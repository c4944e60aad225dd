"""What decides ``correct``, driven through the harness at a size a test
run holds, on the CPU: a sound run passes; the control (the reference
in bfloat16, put in the program's place) and runs with the timed path
broken underneath fail."""
import json
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import correct, data, harness, traffic  # noqa: E402
from bench.reference import fedround  # noqa: E402
from repro.core import executor as executor_lib  # noqa: E402

CELL = "resnet8-cifar10.fedgkd"
SEED = 2 ** 31 + 12345


def tiny(algorithm="fedgkd"):
    """The cell's configuration and traffic at 8x8 images, 4 clients of
    64 examples, batches of 8; its limits as they stand."""
    cell = harness.load_cell(CELL)
    cfg = dict(cell["cfg"], image_hw=8, n_clients=4, participation=0.5,
               train_size=256, test_size=64, batch_size=8, local_epochs=2)
    tr = dict(cell["traffic"], algorithm=algorithm, warmup_rounds=3,
              trace_rounds=1)
    return cfg, tr, cell["limits"]


def run(algorithm="fedgkd"):
    cfg, tr, limits = tiny(algorithm)
    return harness.run_cell(cfg, tr, limits, seed=SEED, seconds=0.5,
                            trace=False, t0=time.perf_counter())


@pytest.mark.parametrize("algorithm", ["fedgkd", "fedavg"])
def test_sound_run_is_correct(algorithm):
    out = run(algorithm)
    assert out["correct"], json.dumps(out["checks"])
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["train_examples_per_s"]["value"] > 0


def test_state_left_unchanged_is_not_correct(monkeypatch):
    """A round that returns its clients' models unchanged."""
    orig = executor_lib.VmapExecutor.run_round

    def unchanged(self, ctx, global_params, *a, **kw):
        out = orig(self, ctx, global_params, *a, **kw)
        out.uploads = [dict(u, params=global_params) for u in out.uploads]
        return out

    monkeypatch.setattr(executor_lib.VmapExecutor, "run_round", unchanged)
    out = run()
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(monkeypatch):
    """Half of every batch left out, the loss the mean over the rest."""
    orig = executor_lib._pad_and_stack

    def half(mats, *a, **kw):
        xs, ys, ex_mask, picks, step_mask = orig(mats, *a, **kw)
        b = ex_mask.shape[2]
        return xs, ys, ex_mask.at[:, :, b // 2:].set(0.0), picks, step_mask

    monkeypatch.setattr(executor_lib, "_pad_and_stack", half)
    assert not run()["correct"]


def test_control_in_bfloat16_is_not_correct():
    """The reference computed in bfloat16 in the program's place."""
    cfg, tr, limits = tiny()
    s = harness.seeds(SEED)
    shards, test_x, test_y = data.make_arrays(cfg, s["data"])
    from repro.core import fl_loop

    model = fl_loop.make_model(harness.paper_task(cfg), width=cfg["width"])
    w0 = harness.make_weights(model, s["weights"])
    steps = traffic.local_steps(cfg)
    kw = dict(shards=shards, test=(test_x, test_y),
              rounds=tr["compare_rounds"], run_seed=s["run"], steps=steps)
    ref = fedround.run(cfg, "fedgkd", w0, **kw)
    ctl = fedround.run(cfg, "fedgkd", w0, dtype=jnp.bfloat16, **kw)
    ctl = dict(ctl, client_params=ctl["client_params"][0])
    ok, shown = correct.verdict(correct.numbers(w0, ctl, ref), limits)
    assert not ok, shown
    assert np.isfinite([v["value"] for v in shown.values()]).all()
