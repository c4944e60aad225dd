#!/usr/bin/env python3
"""Smoke test of the FedGKD round on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py               # one chip: the main path
    python chip_smoke.py --four-chips  # four chips: the shard_map route only

One chip drives ``run_federated`` through its normal entry on the paper's
CIFAR-10 task with ResNet-8 at its published width (16, 32x32 inputs, 10
classes, batch 64), a cohort of K=4 of 20 clients, ``fedgkd`` and
``executor="auto"``, and checks:

* the route resolves to ``vmap`` with the ``client_batched`` round body,
  whose lowered program holds the ``grouped_conv`` and ``kd_kl`` Pallas
  kernels (``tpu_custom_call``);
* per-round losses are finite and fall;
* both kernels (forward and VJP) agree with their ``ref.py`` oracles at the
  main-path shapes;
* one round of the client-batched body agrees with the ``sequential``
  route on the same cohort.

``--four-chips`` runs ``ShardMapExecutor(strict=True)`` over four chips on
the same task and checks it against the single-chip vmap route.

Agreement checks run under ``jax.default_matmul_precision("highest")``, so
the tolerances below bound float32 summation order, not bf16 passes; the
route comparisons train ``COMPARE_STEPS`` local step(s) per client.
Rounds, local steps and the synthetic train-set size are cut (printed
first); widths are not.  Wall times are smoke timings, not benchmark
numbers.  The last line of stdout is one JSON object naming the device;
any failed check exits non-zero before it is printed, and so does a
process that finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    width: int = 16            # ResNet-8 published width
    rounds: int = 5            # paper: 100
    local_steps: int = 4       # paper: 20 local epochs
    train_scale: float = 0.1   # 4,500 of the paper's 45,000 examples
    alpha: float = 0.5         # Dirichlet label skew
    n_test: int = 512
    seed: int = 0


# admitted disagreement at float32 "highest" matmul precision
KERNEL_TOL = 1e-4    # max |kernel - oracle| / max |oracle|: the conv
#                      weight gradient sums 64*32*32 products per element
ROUTE_TOL = 1e-4     # max |a - b| over parameters of magnitude ~0.1-1
# local steps in the route comparisons: programs compiled at "highest"
# precision are slow to compile on the chip (at 4 steps the one-chip
# comparison took over six minutes), so they train one step per client
COMPARE_STEPS = 1

KERNEL_NAMES = ("_grouped_conv_fwd_kernel", "_kd_kl_fwd_kernel")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class Compiles:
    """XLA backend compiles and their seconds (a ``jax.monitoring``
    listener); persistent-cache hits do not count."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


# -- the task ---------------------------------------------------------------

def make_task(sizes: Sizes):
    from repro.configs.paper import CIFAR10, scaled
    from repro.core import fl_loop

    task = scaled(CIFAR10, scale=sizes.train_scale, rounds=sizes.rounds)
    data = fl_loop.make_federated_data(task, alpha=sizes.alpha,
                                       seed=sizes.seed, n_test=sizes.n_test)
    log(f"task: {task.name} model={task.model} width={sizes.width} "
        f"{task.image_hw}x{task.image_hw}x3 classes={task.num_classes} "
        f"batch={task.batch_size} clients={task.n_clients} "
        f"participation={task.participation} (K={cohort(task)}) "
        f"algo=fedgkd")
    log(f"cut: rounds {CIFAR10.rounds} -> {task.rounds}; local steps "
        f"{CIFAR10.local_epochs} epochs -> {sizes.local_steps} batches; "
        f"train set {CIFAR10.train_size} -> {task.train_size} synthetic "
        f"examples (alpha={sizes.alpha}); test set {sizes.n_test}")
    return task, data


def cohort(task) -> int:
    """Clients sampled per round, as ``run_federated`` draws them."""
    return max(1, round(task.participation * task.n_clients))


def run(task, data, sizes: Sizes, executor, rounds: int | None = None):
    from repro.core import algorithms, fl_loop

    return fl_loop.run_federated(
        task, algorithms.make("fedgkd"), data, rounds=rounds,
        seed=sizes.seed, width=sizes.width, executor=executor,
        max_batches_per_client=sizes.local_steps)


def max_param_diff(a, b) -> float:
    import jax
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@contextlib.contextmanager
def wrapped(cls, name: str, wrapper):
    """Route ``cls.name`` through ``wrapper(orig, self, *args)`` for the
    duration of the block: how the smoke sees the arguments and outputs of
    the round body the loop actually ran."""
    orig = getattr(cls, name)
    setattr(cls, name,
            lambda self, *a, **kw: wrapper(orig, self, *a, **kw))
    try:
        yield
    finally:
        setattr(cls, name, orig)


def abstract(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        tree)


# -- phases -----------------------------------------------------------------

def phase_kernels(task, data, sizes: Sizes) -> None:
    """Both kernels against their oracles at the main-path shapes."""
    import jax

    with jax.default_matmul_precision("highest"):
        _check_kernels(task, sizes)


def _check_kernels(task, sizes: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.grouped_conv import ops as conv_ops
    from repro.kernels.grouped_conv import ref as conv_ref
    from repro.kernels.kd_kl import ops as kd_ops
    from repro.kernels.kd_kl import ref as kd_ref
    from repro.models import resnet

    k, b = cohort(task), task.batch_size
    key = jax.random.PRNGKey(sizes.seed)

    def err(got, want):
        """max |got - want|, relative to the oracle's largest magnitude"""
        got, want = np.asarray(got), np.asarray(want)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    def value_and_vjp(f):
        """One jitted program: f's value and its VJP against ``ct``."""
        def run(a, b, ct):
            out, pullback = jax.vjp(f, a, b)
            return (out, *pullback(ct))
        return jax.jit(run)

    # kd_kl: (K*B, classes), forward and the student gradient
    kt, ks, kg = jax.random.split(key, 3)
    t = jax.random.normal(kt, (k * b, task.num_classes)) * 3.0
    s = jax.random.normal(ks, (k * b, task.num_classes)) * 3.0
    g = jax.random.normal(kg, (k * b,))
    got = value_and_vjp(lambda t, s: kd_ops.kd_kl_loss(
        t, s, use_pallas=True))(t, s, g)
    want = value_and_vjp(lambda t, s: kd_ref.kd_kl_rowwise(
        jax.lax.stop_gradient(t), s))(t, s, g)
    e_fwd, e_vjp = err(got[0], want[0]), err(got[2], want[2])
    log(f"agree kd_kl ({k * b}, {task.num_classes}): rel err fwd={e_fwd:.3e}"
        f" vjp={e_vjp:.3e} (tol {KERNEL_TOL:g})")
    check(e_fwd <= KERNEL_TOL and e_vjp <= KERNEL_TOL,
          "kd_kl kernel disagrees with its oracle")

    # grouped_conv: every conv shape ResNet-8 runs on the client-batched body
    shapes = []
    orig = conv_ops.client_batched_conv

    def spy(x, w, *, stride=1, padding="SAME", **kw):
        shapes.append((x.shape, w.shape, stride, padding))
        return orig(x, w, stride=stride, padding=padding, use_pallas=False)

    conv_ops.client_batched_conv = spy
    try:
        params = jax.eval_shape(lambda: jax.vmap(
            lambda r: resnet.resnet8_init(r, task.num_classes,
                                          width=sizes.width))(
            jax.random.split(key, k)))
        jax.eval_shape(resnet.resnet8_apply, params, jax.ShapeDtypeStruct(
            (k, b, task.image_hw, task.image_hw, 3), jnp.float32))
    finally:
        conv_ops.client_batched_conv = orig
    for i, (xs, ws, stride, pad) in enumerate(dict.fromkeys(shapes)):
        kx, kw_, kd = jax.random.split(jax.random.fold_in(key, i), 3)
        x = jax.random.normal(kx, xs)
        w = jax.random.normal(kw_, ws) * 0.1
        dy = jax.random.normal(kd, jax.eval_shape(
            lambda x, w: conv_ref.naive_vmap_conv(x, w, stride, pad),
            x, w).shape)
        got = value_and_vjp(lambda x, w: conv_ops.client_batched_conv(
            x, w, stride=stride, padding=pad, use_pallas=True))(x, w, dy)
        want = value_and_vjp(lambda x, w: conv_ref.naive_vmap_conv(
            x, w, stride, pad))(x, w, dy)
        e_fwd, e_dx, e_dw = (err(a, b) for a, b in zip(got, want))
        log(f"agree grouped_conv x{tuple(xs)} w{tuple(ws)} s{stride}: "
            f"rel err fwd={e_fwd:.3e} dx={e_dx:.3e} dw={e_dw:.3e} "
            f"(tol {KERNEL_TOL:g})")
        check(max(e_fwd, e_dx, e_dw) <= KERNEL_TOL,
              f"grouped_conv disagrees with its oracle at x{xs} w{ws}")


def phase_main(task, data, sizes: Sizes) -> None:
    """The main path: auto route, kernels in the round body, falling loss."""
    from repro.core import executor as executor_lib

    seen = {}

    def record(orig, self, ctx, *args):
        seen.setdefault("round", (ctx, abstract(args)))
        return orig(self, ctx, *args)

    t0 = time.perf_counter()
    with wrapped(executor_lib.VmapExecutor, "_execute", record):
        h = run(task, data, sizes, "auto")
    wall = time.perf_counter() - t0
    tele = h.telemetry
    log(f"route={tele.get('route')} round_body={tele.get('round_body')} "
        f"compile_count={tele.get('compile_count')}")
    check(tele.get("route") == "vmap", f"auto resolved to {tele.get('route')}")
    check(tele.get("round_body") == "client_batched",
          f"round body is {tele.get('round_body')}")
    losses = [r.mean_local_loss for r in h.records]
    for r in h.records:
        log(f"round {r.round}: mean_local_loss={r.mean_local_loss!r} "
            f"test_loss={r.test_loss!r} test_acc={r.test_acc!r} "
            f"smoke_wall_s={r.seconds!r}")
    log(f"smoke wall time, main path (not a benchmark): {wall!r} s")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    ctx, args = seen["round"]
    text = executor_lib.VmapExecutor()._round_fn(ctx).lower(
        *args, ctx.lr).as_text()
    found = {n: f'kernel_name = "{n}"' in text for n in KERNEL_NAMES}
    log(f"round body HLO: tpu_custom_call={'tpu_custom_call' in text} "
        f"kernels={found}")
    check("tpu_custom_call" in text and all(found.values()),
          "round body lowered without the Pallas kernels")


def phase_vs_sequential(task, data, sizes: Sizes) -> None:
    """One round, same cohort: the client-batched body vs the sequential
    reference route."""
    import jax

    sizes = dataclasses.replace(sizes, local_steps=COMPARE_STEPS)
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        hb = run(task, data, sizes, "auto", rounds=1)
        t1 = time.perf_counter()
        hs = run(task, data, sizes, "sequential", rounds=1)
        t2 = time.perf_counter()
    log(f"smoke wall time (not a benchmark): client_batched {t1 - t0:.1f} s,"
        f" sequential {t2 - t1:.1f} s")
    check(hb.records[0].sampled == hs.records[0].sampled,
          "routes sampled different cohorts")
    diff = max_param_diff(hb.final_params, hs.final_params)
    log(f"client_batched vs sequential, 1 round of {COMPARE_STEPS} local "
        f"step(s), cohort "
        f"{list(hb.records[0].sampled)}: max|dparam|={diff:.3e} "
        f"(tol {ROUTE_TOL:g}, highest precision)")
    check(hs.telemetry.get("route") == "sequential", "reference route")
    check(diff <= ROUTE_TOL, "client-batched round disagrees with sequential")


def phase_four_chips(task, data, sizes: Sizes) -> None:
    """ShardMapExecutor(strict=True) over four chips vs the one-chip vmap
    route, same seed and cohorts, over two rounds (the second reuses the
    device-resident client slabs).  Float32 rounding differences between
    the routes grow with every round, so the comparison stops there."""
    import jax

    from repro.core import executor as executor_lib

    sizes = dataclasses.replace(sizes, local_steps=COMPARE_STEPS)
    devices = set(jax.devices())
    check(len(devices) == 4, f"--four-chips needs 4 devices, found "
          f"{len(devices)}")
    placed = []

    def record(orig, self, ctx, mesh):
        fn = orig(self, ctx, mesh)

        def call(*args):
            out = fn(*args)
            placed.extend(({s.device for s in leaf.addressable_shards},
                           leaf.sharding.is_fully_replicated)
                          for leaf in jax.tree.leaves(out))
            return out
        return call

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        with wrapped(executor_lib.ShardMapExecutor, "_sharded_round_fn",
                     record):
            hm = run(task, data, sizes,
                     executor_lib.ShardMapExecutor(strict=True), rounds=2)
        wall = time.perf_counter() - t0
        hv = run(task, data, sizes, "vmap", rounds=2)
    tele = hm.telemetry
    log(f"shard_map: route={tele.get('route')} n_devices="
        f"{tele.get('n_devices')} cohort={tele.get('cohort')} padded_to="
        f"{tele.get('padded_to')} round_body={tele.get('round_body')} "
        f"compile_count={tele.get('compile_count')}")
    for r in hm.records:
        log(f"round {r.round}: mean_local_loss={r.mean_local_loss!r} "
            f"test_acc={r.test_acc!r} smoke_wall_s={r.seconds!r}")
    log(f"smoke wall time, shard_map run (not a benchmark): {wall!r} s")
    check(tele.get("route") == "shard_map" and tele.get("n_devices") == 4,
          "shard_map did not run on four devices")
    check(tele.get("round_body") == "client_batched", "round body")
    check(bool(placed) and all(devs == devices and not repl
                               for devs, repl in placed),
          "round outputs not split over all four devices")
    log(f"round outputs: {len(placed)} leaves, each split over "
        f"{len(devices)} devices")
    check([r.sampled for r in hm.records] == [r.sampled for r in hv.records],
          "routes sampled different cohorts")
    diff = max_param_diff(hm.final_params, hv.final_params)
    log(f"shard_map(4 chips) vs vmap(1 chip), {len(hm.records)} rounds of "
        f"{COMPARE_STEPS} local step(s): "
        f"max|dparam|={diff:.3e} (tol {ROUTE_TOL:g}, highest precision)")
    losses = [r.mean_local_loss for r in hm.records]
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(diff <= ROUTE_TOL, "shard_map disagrees with the vmap route")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map route over four chips "
                         "and its one-chip vmap comparison")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); this "
              f"smoke runs on the chip only", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    sizes = Sizes()
    task, data = make_task(sizes)
    phases = ([phase_four_chips] if args.four_chips else
              [phase_kernels, phase_main, phase_vs_sequential])
    for phase in phases:
        t0, c0, s0 = time.perf_counter(), compiles.count, compiles.seconds
        log(f"== {phase.__name__}")
        phase(task, data, sizes)
        log(f"== {phase.__name__} ok, smoke wall time "
            f"{time.perf_counter() - t0:.1f} s; {compiles.count - c0} XLA "
            f"compiles, {compiles.seconds - s0:.1f} s of them")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
