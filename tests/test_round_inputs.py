"""The batched round's inputs on the device: each local step gathers its
(K, B, ...) batch from the cohort's full shards by its picks, and the
teacher precompute runs over the shards in chunks of rows.

The reference for a round is the host-stacked round the executors ran
before: every step's batch gathered on the host into a (K, S, B, ...)
stack, here handed to the same round body as a "shard" of S x B rows
whose picks are the identity, so the same rows go into the same steps.
The two agree to 1e-6 relative and not bit for bit only where XLA fuses
the device gather into the first convolution differently from a plain
read of the stack.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper import CIFAR10, TINY_IMAGENET
from repro.core import algorithms, executor as ex, fl_loop, tracing
from repro.data.pipeline import ClientData
from repro.optim import sgd

SIZES = (20, 13, 7)          # batch 8: a wrapped last batch (20), a ragged
BATCH = 8                    # batch of 7, and clients with 3, 2, 1 steps
HW = 16
CLASSES = 200
REL = 1e-6

TASKS = {"resnet8": CIFAR10, "resnet50": TINY_IMAGENET}


def _setup(model_name, algo_name="fedavg"):
    task = dataclasses.replace(TASKS[model_name], image_hw=HW,
                               num_classes=CLASSES, batch_size=BATCH,
                               local_epochs=1)
    algo = algorithms.make(algo_name)
    model = fl_loop.make_model(task, algo.needs_projection_head, width=16)
    ctx = ex.RoundContext(algo=algo, model=model, opt=sgd(momentum=0.9),
                          lr=0.05, batch_size=BATCH, epochs=1)
    rng = np.random.default_rng(0)
    clients = [ClientData(rng.standard_normal((n, HW, HW, 3))
                          .astype(np.float32),
                          rng.integers(0, CLASSES, n))
               for n in SIZES]
    params = model.init(jax.random.PRNGKey(0))
    server = algo.init_server(params, model, CLASSES)
    payload = algo.round_payload(server, jax.random.PRNGKey(1))
    states = [algo.init_client_state(i, params) for i in range(len(SIZES))]
    picks = [ex.materialize_picks(rng, d, BATCH, 1) for d in clients]
    return ctx, clients, params, payload, states, picks


def _host_stacked_round(ctx, clients, params, payload, states, picks):
    """The round on a host-gathered (K, S, B, ...) batch stack."""
    k, s = len(clients), max(p.shape[0] for p in picks)
    xs = np.zeros((k, s, BATCH, HW, HW, 3), np.float32)
    ys = np.zeros((k, s, BATCH), np.int32)
    for i, (d, p) in enumerate(zip(clients, picks)):
        xs[i, :p.shape[0], :p.shape[1]] = d.x[p]
        ys[i, :p.shape[0], :p.shape[1]] = d.y[p]
    _, ex_mask, step_mask = ex._pad_and_stack_picks(picks, k)
    identity = np.broadcast_to(
        np.arange(s * BATCH, dtype=np.int32).reshape(s, BATCH), (k, s, BATCH))
    body = ex.VmapExecutor()._round_fn(ctx)
    new, _ = body(params, payload, ex.tree_stack(states),
                  jnp.asarray(xs.reshape(k, s * BATCH, -1)),
                  jnp.asarray(ys.reshape(k, s * BATCH)),
                  jnp.asarray(identity), jnp.asarray(ex_mask), (),
                  jnp.asarray(step_mask), ctx.lr, x_shape=(HW, HW, 3))
    return [jax.tree_util.tree_map(lambda l: l[i], new) for i in range(k)]


def _rel_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


@pytest.mark.parametrize("route", ["vmap", "shard_map"])
@pytest.mark.parametrize("model_name", ["resnet8", "resnet50"])
def test_device_gather_matches_host_stacked_round(model_name, route):
    ctx, clients, params, payload, states, picks = _setup(model_name)
    assert ctx.batched_local_update is not None
    want = _host_stacked_round(ctx, clients, params, payload, states, picks)
    rng = np.random.default_rng(1)
    if route == "vmap":
        out = ex.VmapExecutor().run_round(ctx, params, payload, states,
                                          clients, rng, picks=picks)
    else:
        # the shard_map route's own body over a one-device mesh
        out = ex.ShardMapExecutor()._run_sharded(
            ctx, params, payload, states, clients, rng, None, 1,
            picks=picks)
    assert out.weights == [float(n) for n in SIZES]
    for got, ref in zip(out.uploads, want):
        assert _rel_diff(got["params"], ref) <= REL
    # every client trained: none of the masks left its model unchanged
    assert all(_rel_diff(u["params"], params) > 0 for u in out.uploads)


def test_round_builds_no_batch_stack(monkeypatch):
    """What the vmap route sends to the device is the full shards, one
    flattened row per example, and the (K, S, B) picks: no array with a
    step axis and an example's shape."""
    ctx, clients, params, payload, states, picks = _setup("resnet8")
    seen = []
    orig = ex._pad_and_stack

    def spy(*a, **kw):
        out = orig(*a, **kw)
        seen.append([tuple(v.shape) for v in out])
        return out

    monkeypatch.setattr(ex, "_pad_and_stack", spy)
    rec = tracing.enable()
    try:
        with tracing.round_span(1):
            ex.VmapExecutor().run_round(ctx, params, payload, states,
                                        clients, np.random.default_rng(1),
                                        picks=picks)
    finally:
        tracing.disable()
    rows = max(SIZES)
    assert seen == [[(3, rows, HW * HW * 3), (3, rows), (3, 3, BATCH),
                     (3, 3, BATCH), (3, 3)]]
    assert rec.counter("input_upload_bytes", [1]) == [3 * rows * HW * HW * 3
                                                       * 4]


@pytest.mark.parametrize("chunk", [4, 3])
def test_chunked_precompute_matches_one_teacher_pass(monkeypatch, chunk):
    """10 rows a client in chunks of 4 (3 chunks, the last with 2 real
    rows) or 3 (4 chunks, the last with 1): the teacher's logits equal
    one forward pass over the whole shards.  The chunks' convolutions see
    fewer examples at once than the one pass, so XLA's blocking of the
    sums may differ: 1e-6 relative."""
    monkeypatch.setattr(ex, "PRECOMPUTE_CHUNK", chunk)
    ctx, clients, params, payload, states, _ = _setup("resnet8", "fedgkd")
    sub = [ClientData(d.x[:10], d.y[:10]) if d.n >= 10 else d
           for d in clients]
    fx, fy, fmask = ex._pad_full_data(sub)
    rows = fx.shape[1]
    assert rows == math.ceil(10 / chunk) * chunk
    whole = fx.reshape((len(sub), rows, HW, HW, 3))
    want = jax.vmap(lambda x, y, m: ctx.algo.precompute_aux(
        ctx.model, payload, x, y, m))(whole, fy, fmask)["t_logits"]
    pre = ex.VmapExecutor()._precompute_fn(ctx)
    got = pre(payload, fx, fy, fmask, x_shape=(HW, HW, 3))["t_logits"]
    assert got.shape == want.shape == (len(sub), rows, CLASSES)
    real = np.asarray(fmask, bool)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want)[real])) <= REL * scale
    # the harness's form: whole examples, any row count up to the padding
    n = rows - 1
    short = pre(payload, whole[:, :n], fy[:, :n], fmask[:, :n])["t_logits"]
    assert short.shape == want.shape
    np.testing.assert_array_equal(np.asarray(short[:, :n]),
                                  np.asarray(got[:, :n]))


def test_precompute_counts_its_chunks(monkeypatch):
    monkeypatch.setattr(ex, "PRECOMPUTE_CHUNK", 4)
    ctx, clients, params, payload, states, picks = _setup("resnet8",
                                                          "fedgkd")
    rec = tracing.enable()
    try:
        with tracing.round_span(1):
            ex.VmapExecutor().run_round(ctx, params, payload, states,
                                        clients, np.random.default_rng(1),
                                        picks=picks)
    finally:
        tracing.disable()
    assert rec.counter("precompute_chunks", [1]) == [
        math.ceil(max(SIZES) / 4)]
