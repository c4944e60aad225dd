"""ClientExecutor subsystem: sequential/vmap equivalence, padding masks,
batch materialization, resolution rules and the fl_loop fast paths.

Runs on the TOY mlp task (fast compiles) with hand-built ragged client
sizes so both mask kinds are exercised deterministically: clients smaller
than the batch size (example padding) and clients with fewer steps than
the cohort max (step padding).

The multi-device section at the bottom needs several visible devices; the
CI ``multidevice`` job (and a local repro) provides them with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — set BEFORE the
first jax import.  On a single-device run those tests skip, and one
subprocess smoke test keeps the mesh route exercised regardless."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper import TOY
from repro.core import algorithms, executor as ex, fl_loop
from repro.core.distillation import cross_entropy
from repro.data.pipeline import (ClientData, ClientSlabStore, FederatedData,
                                 SLAB_QUANT, batch_iterator, slab_rows)
from repro.data.synthetic import SyntheticTabularTask


RAGGED_SIZES = (20, 45, 64, 100, 130, 150)   # 20 < batch 64 < 150
RAGGED_SIZES_8 = RAGGED_SIZES + (90, 33)     # K=8: divides an 8-device mesh


def _ragged_data(task, sizes):
    gen = SyntheticTabularTask(task.num_classes, dim=task.feat_dim, seed=0)
    clients = [ClientData(*gen.generate(n, seed=100 + i))
               for i, n in enumerate(sizes)]
    test_x, test_y = gen.generate(200, seed=999)
    return FederatedData(clients, test_x, test_y,
                         np.zeros((len(sizes), task.num_classes)))


multidevice = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def tiny_setup():
    task = dataclasses.replace(TOY, n_clients=len(RAGGED_SIZES),
                               participation=1.0, batch_size=64, rounds=2,
                               local_epochs=2)
    return task, _ragged_data(task, RAGGED_SIZES)


def _max_param_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# --- numerical equivalence (the acceptance criterion) ----------------------

@pytest.mark.parametrize("name", ["fedavg", "fedprox", "fedgkd"])
def test_vmap_matches_sequential(tiny_setup, name):
    task, data = tiny_setup
    sizes = {c.n for c in data.clients}
    assert min(sizes) < task.batch_size < max(sizes), \
        "setup must exercise example- AND step-level padding masks"
    out = {}
    for spec in ("sequential", "vmap"):
        h = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                                  executor=spec)
        out[spec] = h
    assert _max_param_diff(out["sequential"].final_params,
                           out["vmap"].final_params) < 1e-5
    for rs, rv in zip(out["sequential"].records, out["vmap"].records):
        assert abs(rs.mean_local_loss - rv.mean_local_loss) < 1e-5
        assert abs(rs.test_acc - rv.test_acc) < 1e-5


def test_shard_map_executor_matches_sequential(tiny_setup):
    """On one device the executor degrades to the vmap computation; on a
    multi-device host (the CI ``multidevice`` job) this exercises the real
    mesh route with a non-dividing cohort.  Either way: < 1e-5."""
    task, data = tiny_setup
    hs = fl_loop.run_federated(task, algorithms.make("fedgkd"), data, seed=0,
                               executor="sequential")
    hm = fl_loop.run_federated(task, algorithms.make("fedgkd"), data, seed=0,
                               executor="shard_map")
    assert _max_param_diff(hs.final_params, hm.final_params) < 1e-5


# --- async equivalence (the PR-4 tentpole) ----------------------------------
#
# In the degenerate regime — homogeneous speeds, full buffer B == cohort,
# zero staleness — the buffered-async loop must reproduce the synchronous
# executors: same sampling, same batch draws, same aggregation order.

@pytest.mark.parametrize("name", ["fedavg", "fedprox", "fedgkd",
                                  "fedgkd-vote"])
def test_async_matches_sequential(tiny_setup, name):
    task, data = tiny_setup
    hs = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                               rounds=3, executor="sequential")
    ha = fl_loop.run_federated(
        task, algorithms.make(name), data, seed=0, rounds=3,
        executor=ex.AsyncExecutor(staleness="constant"))
    assert _max_param_diff(hs.final_params, ha.final_params) < 1e-5
    for rs, ra in zip(hs.records, ha.records):
        assert abs(rs.mean_local_loss - ra.mean_local_loss) < 1e-5
        assert abs(rs.test_acc - ra.test_acc) < 1e-5
        assert rs.sampled == ra.sampled     # same cohorts, same order
    assert all(r.mean_staleness == 0.0 for r in ha.records)


def test_async_sequential_inner_is_bit_identical(tiny_setup):
    """With the SEQUENTIAL inner executor there is no vmap associativity
    left: the async loop in the degenerate regime is the same computation
    in the same order — bit-identical, not just < 1e-5."""
    task, data = tiny_setup
    hs = fl_loop.run_federated(task, algorithms.make("fedgkd"), data, seed=0,
                               rounds=2, executor="sequential")
    ha = fl_loop.run_federated(
        task, algorithms.make("fedgkd"), data, seed=0, rounds=2,
        executor=ex.AsyncExecutor(staleness="constant", inner="sequential"))
    assert _max_param_diff(hs.final_params, ha.final_params) == 0.0
    for rs, ra in zip(hs.records, ha.records):
        assert rs.test_acc == ra.test_acc
        assert rs.mean_local_loss == ra.mean_local_loss


@multidevice
@pytest.mark.parametrize("name", ["fedavg", "fedgkd-vote"])
def test_async_shard_map_inner_matches_sequential(tiny_setup, name):
    """The CI multidevice gate: async ready-cohorts on the strict mesh
    route (K=6 padded onto 8 devices, device-resident slabs, sharded
    teacher precompute) still reproduce the sequential reference."""
    task, data = tiny_setup
    hs = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                               rounds=3, executor="sequential")
    ha = fl_loop.run_federated(
        task, algorithms.make(name), data, seed=0, rounds=3,
        executor=ex.AsyncExecutor(
            staleness="constant", inner=ex.ShardMapExecutor(strict=True)))
    assert ha.telemetry["inner_route"] == "shard_map"
    assert _max_param_diff(hs.final_params, ha.final_params) < 1e-5
    for rs, ra in zip(hs.records, ha.records):
        assert abs(rs.mean_local_loss - ra.mean_local_loss) < 1e-5


# --- round-level teacher precompute (the PR-2 tentpole) ---------------------

@pytest.mark.parametrize("name", ["fedgkd", "fedgkd-vote", "feddistill+"])
def test_precompute_matches_no_aux_baseline(tiny_setup, name):
    """Sequential/vmap with the precompute_aux stage must reproduce the PR-1
    inline-teacher (no-aux) execution to < 1e-5 on params, losses and acc."""
    task, data = tiny_setup
    algo = algorithms.make(name)
    assert type(algo).precompute_aux is not algorithms.Algorithm.precompute_aux
    base = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                                 executor="sequential", precompute=False)
    for spec in ("sequential", "vmap"):
        h = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                                  executor=spec, precompute=True)
        assert _max_param_diff(base.final_params, h.final_params) < 1e-5, spec
        for rb, rh in zip(base.records, h.records):
            assert abs(rb.mean_local_loss - rh.mean_local_loss) < 1e-5, spec
            assert abs(rb.test_acc - rh.test_acc) < 1e-5, spec


def test_precompute_flag_gates_hook(tiny_setup):
    """precompute=False must force has_precompute off even for KD algos; a
    no-hook algorithm never precomputes."""
    task, _ = tiny_setup
    from repro.core.modelzoo import make_model
    from repro.optim import sgd
    model = make_model(task)
    mk = lambda algo, pre: ex.RoundContext(
        algo=algo, model=model, opt=sgd(), lr=0.1, batch_size=64, epochs=1,
        precompute=pre)
    assert mk(algorithms.make("fedgkd"), True).has_precompute
    assert not mk(algorithms.make("fedgkd"), False).has_precompute
    assert not mk(algorithms.make("fedavg"), True).has_precompute


def test_precompute_aux_values_match_inline_teacher(tiny_setup):
    """The gathered aux rows equal the teacher logits the inline loss would
    compute on the same batch."""
    task, data = tiny_setup
    from repro.core.modelzoo import make_model
    model = make_model(task)
    params = model.init(jax.random.PRNGKey(0))
    gkd = algorithms.make("fedgkd", buffer_m=1)
    server = gkd.init_server(params, model, task.num_classes)
    payload = gkd.round_payload(server, jax.random.PRNGKey(1))
    cdata = data.clients[0]
    aux = gkd.precompute_aux(model, payload, jnp.asarray(cdata.x),
                             jnp.asarray(cdata.y),
                             jnp.ones((cdata.n,), jnp.float32))
    rng = np.random.default_rng(5)
    mat = ex.materialize_client(rng, cdata, batch_size=8, epochs=1)
    direct = model.apply(payload["teacher"], jnp.asarray(mat.xs[0]))
    np.testing.assert_allclose(np.asarray(aux["t_logits"][mat.picks[0]]),
                               np.asarray(direct), atol=1e-6)


@pytest.mark.parametrize("name", ["moon", "scaffold", "feddyn",
                                  "feddistill+"])
def test_stateful_algorithms_run_under_vmap(tiny_setup, name):
    task, data = tiny_setup
    h = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                              executor="vmap")
    assert np.isfinite(h.final_acc)
    assert np.isfinite(h.records[-1].mean_local_loss)


# --- masking exactness ------------------------------------------------------

def test_masked_loss_ignores_padded_examples(tiny_setup):
    task, _ = tiny_setup
    from repro.core.modelzoo import make_model
    model = make_model(task)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, task.feat_dim)), jnp.float32)
    y = jnp.asarray([0, 1, 2, 3])
    loss = algorithms.make("fedavg").loss_fn(model)
    l_real, _ = loss(params, (), (), x[:2], y[:2], jnp.ones((2,)))
    x_pad = jnp.concatenate([x[:2], jnp.zeros_like(x[:2])])
    l_pad, _ = loss(params, (), (), x_pad, y,
                    jnp.asarray([1.0, 1.0, 0.0, 0.0]))
    np.testing.assert_allclose(float(l_real), float(l_pad), atol=1e-7)


def test_masked_step_is_identity(tiny_setup):
    """A fully padded scan step must leave params AND opt state untouched."""
    task, _ = tiny_setup
    from repro.core import client as client_lib
    from repro.core.modelzoo import make_model
    from repro.optim import sgd
    model = make_model(task)
    params = model.init(jax.random.PRNGKey(1))
    local = client_lib.make_local_update(
        algorithms.make("fedavg").loss_fn(model), sgd(momentum=0.9))
    xs = jnp.zeros((2, 3, task.feat_dim))
    ys = jnp.zeros((2, 3), jnp.int32)
    ex_mask = jnp.zeros((2, 3), jnp.float32)
    step_mask = jnp.zeros((2,), bool)
    new_params, mloss = jax.jit(local)(params, (), (), xs, ys, ex_mask, (),
                                       step_mask, 0.1)
    assert _max_param_diff(params, new_params) == 0.0
    assert float(mloss) == 0.0


# --- batch materialization --------------------------------------------------

def test_materialize_matches_batch_iterator():
    rng_a = np.random.default_rng(7)
    rng_b = np.random.default_rng(7)
    data = ClientData(np.arange(22, dtype=np.float32).reshape(22, 1),
                      np.arange(22) % 3)
    mat = ex.materialize_client(rng_a, data, batch_size=8, epochs=2)
    ref = list(batch_iterator(rng_b, data, 8, 2))
    assert mat.xs.shape[0] == len(ref)
    for s, (x, y) in enumerate(ref):
        np.testing.assert_array_equal(mat.xs[s], x)
        np.testing.assert_array_equal(mat.ys[s], y)


def test_materialize_max_batches_rng_consumption():
    """Stopping early must not draw later epochs' permutations (so a given
    seed produces identical batches whether or not max_batches is set)."""
    rng_a = np.random.default_rng(9)
    rng_b = np.random.default_rng(9)
    data = ClientData(np.arange(10, dtype=np.float32).reshape(10, 1),
                      np.arange(10) % 2)
    mat = ex.materialize_client(rng_a, data, batch_size=4, epochs=5,
                                max_batches=2)
    assert mat.xs.shape[0] == 2
    full = ex.materialize_client(rng_b, data, batch_size=4, epochs=1)
    np.testing.assert_array_equal(mat.xs, full.xs[:2])


def test_pad_and_stack_masks():
    mk = lambda s, b: ex.MaterializedClient(
        np.ones((s, b, 2), np.float32), np.ones((s, b), np.int64), s * b,
        np.arange(s * b, dtype=np.int32).reshape(s, b) % max(1, s * b // 2))
    xs, ys, ex_mask, picks, step_mask = ex._pad_and_stack([mk(3, 4), mk(1, 2)])
    assert xs.shape == (2, 3, 4, 2)
    assert picks.shape == (2, 3, 4)
    assert float(ex_mask[0].sum()) == 12.0
    assert float(ex_mask[1].sum()) == 2.0
    assert step_mask.tolist() == [[True, True, True], [True, False, False]]
    # padded pick slots are in-range gathers (row 0) for the masked examples
    assert int(picks[1, 1:].max()) == 0


# --- resolution / fl_loop plumbing -----------------------------------------

def test_get_executor_resolution():
    from repro.core.modelzoo import ModelBundle
    avg = algorithms.make("fedavg")
    assert ex.get_executor("auto", avg, 4).name == "vmap"
    assert ex.get_executor("auto", avg, 1).name == "sequential"
    conv = ModelBundle("resnet8", lambda r: {}, lambda p, x: x,
                       lambda p, x: x, vmap_friendly=False)
    assert ex.get_executor("auto", avg, 4, conv).name == "sequential"
    no_vmap = algorithms.make("fedavg")
    no_vmap.supports_vmap = False
    assert ex.get_executor("auto", no_vmap, 4).name == "sequential"
    inst = ex.SequentialExecutor()
    assert ex.get_executor(inst, avg, 4) is inst
    with pytest.raises(ValueError):
        ex.get_executor("nope", avg, 4)
    # the async executor resolves by name; its READY-COHORT inner executor
    # resolves through the same rules
    a = ex.get_executor("async", avg, 4)
    assert isinstance(a, ex.AsyncExecutor)
    assert "async" in ex.available()
    assert a.resolve_inner(avg, 4).name == "vmap"
    assert a.resolve_inner(avg, 1).name == "sequential"
    with pytest.raises(NotImplementedError):
        a.run_round(None, None, None, [], [], np.random.default_rng(0))
    with pytest.raises(ValueError):
        ex.AsyncExecutor(inner="async")
    with pytest.raises(ValueError):
        ex.AsyncExecutor(staleness="nope")


def test_zero_rounds_fast_path(tiny_setup):
    task, data = tiny_setup
    h = fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                              rounds=0)
    assert h.records == []
    assert h.local_model_acc == 0.0
    assert h.final_params is not None


def test_evaluate_apply_cache(tiny_setup):
    task, data = tiny_setup
    from repro.core.modelzoo import make_model
    model = make_model(task)
    params = model.init(jax.random.PRNGKey(0))
    fl_loop.evaluate(model, params, data.test_x[:32], data.test_y[:32])
    fn = fl_loop._APPLY_CACHE.get(model.apply)
    assert fn is not None
    model2 = make_model(task)     # same backbone => same cached wrapper
    fl_loop.evaluate(model2, params, data.test_x[:32], data.test_y[:32])
    assert fl_loop._APPLY_CACHE.get(model2.apply) is fn


def _plain_evaluate(model, params, x, y, batch=256):
    """The per-batch loop ``fl_loop.evaluate`` replaced: one ``apply`` and
    two host reads a batch; the loss is each batch's mean weighted by its
    rows."""
    apply = jax.jit(model.apply)
    correct, loss = 0, 0.0
    for i in range(0, len(y), batch):
        logits = apply(params, jnp.asarray(x[i:i + batch]))
        yb = y[i:i + batch]
        correct += int(np.sum(np.argmax(np.asarray(logits), -1) == yb))
        loss += float(cross_entropy(logits, jnp.asarray(yb))) * len(yb)
    return correct / len(y), loss / len(y)


def _eval_bundle(name):
    if name == "mlp":
        task = TOY
        gen = SyntheticTabularTask(task.num_classes, dim=task.feat_dim,
                                   seed=0)
        return task, fl_loop.make_model(task), gen
    from repro.configs.paper import CIFAR10
    from repro.data.synthetic import SyntheticImageTask
    task = dataclasses.replace(CIFAR10, image_hw=16)
    return (task, fl_loop.make_model(task, width=8),
            SyntheticImageTask(task.num_classes, hw=16, seed=0))


@pytest.mark.parametrize("n", [100, 512, 300],
                         ids=["under_batch", "multiple", "ragged"])
@pytest.mark.parametrize("bundle", ["mlp", "resnet8"])
def test_evaluate_matches_plain_batch_loop(bundle, n):
    """One program over the padded resident test set gives the plain
    loop's accuracy exactly and its loss to float32 rounding, with
    ignore-labelled rows counted in ``n`` as before."""
    task, model, gen = _eval_bundle(bundle)
    if bundle == "resnet8":
        assert model.client_batched
    params = model.init(jax.random.PRNGKey(3))
    x, y = gen.generate(n, seed=7)
    y = np.where(np.arange(n) % 7 == 3, -1, y)
    acc, loss = fl_loop.evaluate(model, params, x, y)
    ref_acc, ref_loss = _plain_evaluate(model, params, x, y)
    assert acc == ref_acc
    assert loss == pytest.approx(ref_loss, rel=1e-6)
    assert fl_loop.evaluate(model, params, x, y) == (acc, loss)


# --- slab layout / placement store (single device is enough) ----------------

def test_slab_rows_quantization():
    assert slab_rows(1) == SLAB_QUANT
    assert slab_rows(SLAB_QUANT) == SLAB_QUANT
    assert slab_rows(SLAB_QUANT + 1) == 2 * SLAB_QUANT


def test_slab_store_residency_and_counters():
    dev = jax.devices()[0]
    store = ClientSlabStore()
    data = ClientData(np.arange(40, dtype=np.float32).reshape(20, 2),
                      np.arange(20) % 3)
    e1 = store.get(7, data, dev)
    assert store.host_transfers == 1 and store.hits == 0
    assert e1["rows"] == slab_rows(20) and e1["n"] == 20
    assert list(e1["x"].devices()) == [dev]
    np.testing.assert_array_equal(np.asarray(e1["x"])[:20], data.x)
    assert np.asarray(e1["y"])[20:].sum() == 0            # zero padding
    e2 = store.get(7, data, dev)                          # resident => hit
    assert e2 is e1 and store.hits == 1 and store.host_transfers == 1
    store.get(None, data, dev)                            # uncached cid
    assert store.host_transfers == 2 and len(store.slabs) == 1
    bigger = ClientData(np.zeros((21, 2), np.float32), np.zeros(21, np.int64))
    store.get(7, bigger, dev)                             # shard grew
    assert store.host_transfers == 3


def test_materialize_picks_matches_materialize_client():
    rng_a, rng_b = np.random.default_rng(3), np.random.default_rng(3)
    data = ClientData(np.arange(44, dtype=np.float32).reshape(22, 2),
                      np.arange(22) % 3)
    picks = ex.materialize_picks(rng_a, data, batch_size=8, epochs=2)
    mat = ex.materialize_client(rng_b, data, batch_size=8, epochs=2)
    np.testing.assert_array_equal(picks, mat.picks)
    np.testing.assert_array_equal(data.x[picks], mat.xs)


def test_pad_and_stack_picks_phantom_clients():
    picks = [np.arange(12, dtype=np.int32).reshape(3, 4),
             np.arange(2, dtype=np.int32).reshape(1, 2)]
    p, ex_mask, step_mask = ex._pad_and_stack_picks(picks, k_pad=4)
    assert p.shape == (4, 3, 4)
    assert float(ex_mask[0].sum()) == 12.0
    assert float(ex_mask[1].sum()) == 2.0
    # phantom clients: every mask zero, every pick an in-range row-0 gather
    assert float(ex_mask[2:].sum()) == 0.0
    assert not step_mask[2:].any()
    assert int(p[2:].max()) == 0


def test_pad_clients_axis():
    tree = {"w": jnp.ones((3, 2)), "b": jnp.ones((3,))}
    out = ex._pad_clients_axis(tree, 5)
    assert out["w"].shape == (5, 2) and out["b"].shape == (5,)
    assert float(out["w"][3:].sum()) == 0.0
    assert ex._pad_clients_axis((), 5) == ()


# --- shard_map route selection / strict mode --------------------------------

def test_shard_map_strict_raises_on_single_device(tiny_setup):
    if len(jax.devices()) != 1:
        pytest.skip("fallback only exists on a single-device host")
    task, data = tiny_setup
    with pytest.raises(RuntimeError, match="strict"):
        fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                              executor=ex.ShardMapExecutor(strict=True))


def test_route_telemetry_records_what_ran(tiny_setup):
    task, data = tiny_setup
    from repro.core.modelzoo import make_model
    from repro.optim import sgd
    model = make_model(task)
    mk = lambda: ex.RoundContext(algo=algorithms.make("fedavg"), model=model,
                                 opt=sgd(), lr=0.1, batch_size=64, epochs=1)
    gp = model.init(jax.random.PRNGKey(0))
    states = [() for _ in data.clients]
    for exec_, want in ((ex.SequentialExecutor(), "sequential"),
                        (ex.VmapExecutor(), "vmap")):
        ctx = mk()
        exec_.run_round(ctx, gp, (), states, data.clients,
                        np.random.default_rng(0))
        assert ctx.telemetry["route"] == want
    ctx = mk()
    ex.ShardMapExecutor().run_round(ctx, gp, (), states, data.clients,
                                    np.random.default_rng(0))
    want = "vmap-fallback" if len(jax.devices()) == 1 else "shard_map"
    assert ctx.telemetry["route"] == want
    assert ctx.telemetry["n_devices"] == len(jax.devices())


# --- the real multi-device path (CI `multidevice` job) ----------------------

@pytest.fixture(scope="module", params=[RAGGED_SIZES, RAGGED_SIZES_8],
                ids=["K6", "K8"])
def cohort_setup(request):
    sizes = request.param
    task = dataclasses.replace(TOY, n_clients=len(sizes), participation=1.0,
                               batch_size=64, rounds=2, local_epochs=2)
    return task, _ragged_data(task, sizes)


@multidevice
@pytest.mark.parametrize("name", ["fedavg", "fedprox", "fedgkd",
                                  "fedgkd-vote"])
def test_shard_map_cohorts_match_sequential(cohort_setup, name):
    """The acceptance criterion: K=6 (non-dividing, padded with phantoms)
    AND K=8 (dividing) ragged cohorts on an 8-device host, strict mode (no
    fallback permitted), < 1e-5 vs the sequential reference."""
    task, data = cohort_setup
    hs = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                               executor="sequential")
    hm = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                               executor=ex.ShardMapExecutor(strict=True))
    assert _max_param_diff(hs.final_params, hm.final_params) < 1e-5
    for rs, rm in zip(hs.records, hm.records):
        assert abs(rs.mean_local_loss - rm.mean_local_loss) < 1e-5
        assert abs(rs.test_acc - rm.test_acc) < 1e-5


@multidevice
def test_shard_map_mixed_member_phantom_shard():
    """K=13 (prime) on any 2..8-device mesh: some device's shard stack
    holds BOTH real clients and phantom padding in the same round — the
    members/phantom split boundary inside ``_resident_cohort``."""
    sizes = RAGGED_SIZES + (90, 33, 70, 25, 140, 55, 80)
    k, ndev = len(sizes), len(jax.devices())
    assert k == 13
    if k % ndev == 0:
        pytest.skip("needs a device count that does not divide K=13")
    g = -(-k // ndev)
    assert any(0 < k - d * g < g for d in range(ndev)), \
        "setup must yield a device owning real AND phantom clients"
    task = dataclasses.replace(TOY, n_clients=k, participation=1.0,
                               batch_size=64, rounds=1, local_epochs=1)
    data = _ragged_data(task, sizes)
    hs = fl_loop.run_federated(task, algorithms.make("fedgkd"), data, seed=0,
                               executor="sequential")
    hm = fl_loop.run_federated(task, algorithms.make("fedgkd"), data, seed=0,
                               executor=ex.ShardMapExecutor(strict=True))
    assert _max_param_diff(hs.final_params, hm.final_params) < 1e-5


@multidevice
def test_shard_map_parts_cache_survives_cohort_churn(tiny_setup):
    """Partial participation: rotating cohorts must NOT flush the teacher
    part cache — a version is recomputed only when some sampled client has
    never seen it, so full-overlap rotations reassemble instead."""
    task, data = tiny_setup
    from repro.optim import sgd
    m_teachers = 3
    algo, model, gp, payload0, _ = _vote_ctx_and_payloads(task, m_teachers)
    ctx = ex.RoundContext(algo=algo, model=model, opt=sgd(), lr=0.05,
                          batch_size=64, epochs=1)
    exec_ = ex.ShardMapExecutor(strict=True)
    rng = np.random.default_rng(0)
    k = len(data.clients)
    cohorts = [list(range(k)),                 # cold: M forwards
               list(range(k - 1, -1, -1)),     # same clients, new order
               list(range(k))]                 # back again
    for cohort in cohorts:
        exec_.run_round(ctx, gp, payload0, [() for _ in cohort],
                        [data.clients[c] for c in cohort], rng,
                        client_ids=cohort)
    assert ctx.telemetry["parts_computed"] == m_teachers, \
        "cohort churn with full overlap must reassemble, not recompute"


def test_slab_store_lru_eviction():
    dev = jax.devices()[0]
    from repro.data.pipeline import ClientSlabStore as Store
    store = Store(max_resident=2)
    mk = lambda n: ClientData(np.zeros((n, 2), np.float32),
                              np.zeros(n, np.int64))
    store.get(0, mk(8), dev)
    store.get(1, mk(8), dev)
    store.get(0, mk(8), dev)          # refresh 0 -> 1 is now LRU
    store.get(2, mk(8), dev)          # evicts 1
    assert set(store.slabs) == {0, 2}
    assert store.evictions == 1
    store.get(1, mk(8), dev)          # re-upload after eviction
    assert store.host_transfers == 4


@multidevice
def test_shard_map_strict_never_falls_back(tiny_setup):
    """Regression for the PR-2 silent-fallback footgun: K=6 on a
    multi-device host pads to the mesh and runs shard_map, never vmap."""
    task, data = tiny_setup
    from repro.core.modelzoo import make_model
    from repro.optim import sgd
    algo = algorithms.make("fedavg")
    model = make_model(task)
    ctx = ex.RoundContext(algo=algo, model=model, opt=sgd(), lr=0.1,
                          batch_size=64, epochs=1)
    gp = model.init(jax.random.PRNGKey(0))
    ex.ShardMapExecutor(strict=True).run_round(
        ctx, gp, (), [() for _ in data.clients], data.clients,
        np.random.default_rng(0), client_ids=list(range(len(data.clients))))
    ndev = len(jax.devices())
    assert ctx.telemetry["route"] == "shard_map"
    assert ctx.telemetry["cohort"] == len(data.clients)
    assert ctx.telemetry["padded_to"] % ndev == 0
    assert ctx.telemetry["padded_to"] >= len(data.clients)


def _vote_ctx_and_payloads(task, n_teachers=3):
    """A FedGKD-VOTE round context plus payloads before/after one teacher
    rotation (buffer filled so the ensemble is real)."""
    from repro.core.modelzoo import make_model
    from repro.optim import sgd
    algo = algorithms.make("fedgkd-vote", buffer_m=n_teachers)
    model = make_model(task)
    gp = model.init(jax.random.PRNGKey(0))
    server = algo.init_server(gp, model, task.num_classes)
    for m in range(n_teachers - 1):
        server["buffer"].push(jax.tree_util.tree_map(
            lambda p: p * (1.0 + 0.01 * (m + 1)), gp))
    server["val_losses"] = [0.1 * (m + 1) for m in range(n_teachers)]
    p0 = algo.round_payload(server, jax.random.PRNGKey(1))
    server["buffer"].push(jax.tree_util.tree_map(lambda p: p * 1.05, gp))
    p1 = algo.round_payload(server, jax.random.PRNGKey(2))
    return algo, model, gp, p0, p1


@multidevice
def test_shard_map_slab_reuse_and_part_invalidation(tiny_setup):
    """Device-resident reuse: a client sampled in consecutive rounds does
    NOT re-upload its shard; a ModelBuffer version bump invalidates exactly
    the one stale teacher part."""
    task, data = tiny_setup
    from repro.optim import sgd
    m_teachers = 3
    algo, model, gp, payload0, payload1 = _vote_ctx_and_payloads(
        task, m_teachers)
    ctx = ex.RoundContext(algo=algo, model=model, opt=sgd(), lr=0.05,
                          batch_size=64, epochs=1)
    exec_ = ex.ShardMapExecutor(strict=True)
    rng = np.random.default_rng(0)
    states = [() for _ in data.clients]
    cids = list(range(len(data.clients)))
    k = len(data.clients)

    exec_.run_round(ctx, gp, payload0, states, data.clients, rng,
                    client_ids=cids)
    t1 = dict(ctx.telemetry)
    assert t1["placement"]["host_transfers"] == k     # one upload per client
    assert t1["parts_computed"] == m_teachers         # cold cache: M forwards

    exec_.run_round(ctx, gp, payload0, states, data.clients, rng,
                    client_ids=cids)
    t2 = dict(ctx.telemetry)
    assert t2["placement"]["host_transfers"] == k, "shards must stay resident"
    assert t2["parts_computed"] == m_teachers, "all teacher parts cached"

    exec_.run_round(ctx, gp, payload1, states, data.clients, rng,
                    client_ids=cids)                  # ONE teacher rotated
    t3 = dict(ctx.telemetry)
    assert t3["parts_computed"] == m_teachers + 1, \
        "version bump must invalidate exactly the one stale part"
    assert t3["placement"]["host_transfers"] == k

    # placement introspection: every slab pinned to exactly its slot device
    for entry in ctx.placement.slabs.values():
        assert list(entry["x"].devices()) == [entry["device"]]


@multidevice
def test_shard_map_precompute_matches_no_aux_baseline(tiny_setup):
    """The mesh-routed teacher precompute (fedgkd direct + fedgkd-vote
    parts path) must reproduce the inline no-aux loss to < 1e-5."""
    task, data = tiny_setup
    for name in ("fedgkd", "fedgkd-vote"):
        base = fl_loop.run_federated(task, algorithms.make(name), data,
                                     seed=0, executor="sequential",
                                     precompute=False)
        h = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                                  executor=ex.ShardMapExecutor(strict=True),
                                  precompute=True)
        assert _max_param_diff(base.final_params, h.final_params) < 1e-5, name


def _check_mesh_evaluate():
    """``run_federated(executor="shard_map")`` leaves the global model on
    the ("clients",) mesh and evaluates it there: every round's test
    accuracy and loss match the vmap route's to 1e-5, over a test set
    with a ragged last batch."""
    task = dataclasses.replace(TOY, n_clients=len(RAGGED_SIZES_8),
                               participation=1.0, batch_size=64, rounds=2,
                               local_epochs=1)
    data = _ragged_data(task, RAGGED_SIZES_8)
    gen = SyntheticTabularTask(task.num_classes, dim=task.feat_dim, seed=0)
    data = dataclasses.replace(data, **dict(zip(
        ("test_x", "test_y"), gen.generate(300, seed=998))))
    hv = fl_loop.run_federated(task, algorithms.make("fedavg"), data,
                               seed=0, executor="vmap")
    hm = fl_loop.run_federated(task, algorithms.make("fedavg"), data,
                               seed=0, executor="shard_map")
    leaf = jax.tree_util.tree_leaves(hm.final_params)[0]
    assert len(leaf.devices()) == len(jax.devices()) > 1
    for rv, rm in zip(hv.records, hm.records, strict=True):
        assert abs(rv.test_acc - rm.test_acc) < 1e-5
        assert abs(rv.test_loss - rm.test_loss) < 1e-5
    assert fl_loop.evaluate(fl_loop.make_model(task), hm.final_params,
                            data.test_x, data.test_y) == (
        hm.records[-1].test_acc, hm.records[-1].test_loss)


@multidevice
def test_shard_map_evaluates_the_global_on_the_mesh():
    _check_mesh_evaluate()


def test_shard_map_evaluates_the_global_on_the_mesh_subprocess():
    """The same check on 8 forced host devices when the main pytest
    process has one."""
    if len(jax.devices()) >= 2:
        pytest.skip("the in-process test covers the mesh")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=os.path.join(here, "..", "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = (f"import sys; sys.path.insert(0, {here!r}); "
            "import test_executor as t; t._check_mesh_evaluate(); "
            "print('MESH_EVAL_OK')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_EVAL_OK" in out.stdout


# --- subprocess smoke: keeps the mesh route alive on single-device boxes ----

_SMOKE_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import warnings; warnings.filterwarnings("ignore")
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.paper import TOY
    from repro.core import algorithms, executor as ex, fl_loop
    from repro.data.pipeline import ClientData, FederatedData
    from repro.data.synthetic import SyntheticTabularTask

    sizes = (20, 45, 64, 100, 130, 150)
    task = dataclasses.replace(TOY, n_clients=6, participation=1.0,
                               batch_size=64, rounds=1, local_epochs=1)
    gen = SyntheticTabularTask(task.num_classes, dim=task.feat_dim, seed=0)
    clients = [ClientData(*gen.generate(n, seed=100 + i))
               for i, n in enumerate(sizes)]
    tx, ty = gen.generate(100, seed=999)
    data = FederatedData(clients, tx, ty, np.zeros((6, task.num_classes)))
    hs = fl_loop.run_federated(task, algorithms.make("fedgkd"), data,
                               seed=0, executor="sequential")
    hm = fl_loop.run_federated(task, algorithms.make("fedgkd"), data,
                               seed=0,
                               executor=ex.ShardMapExecutor(strict=True))
    d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(hs.final_params),
        jax.tree_util.tree_leaves(hm.final_params)))
    assert len(jax.devices()) == 8, len(jax.devices())
    assert d < 1e-5, d
    print("SMOKE_OK", d)
""")


def test_shard_map_multidevice_subprocess_smoke():
    """Guard for single-device boxes: the strict mesh route (K=6 on 8
    forced host devices) must run without fallback and match sequential
    even when the main pytest process has one device.  The CI multidevice
    job covers the full matrix in-process, so there this subprocess rerun
    would only duplicate coverage — skip it."""
    if len(jax.devices()) >= 2:
        pytest.skip("in-process multidevice tests already cover the route")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SMOKE_SNIPPET],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SMOKE_OK" in out.stdout


# --- the client-batched conv route (resnet8, kernels/grouped_conv) ----------
#
# The paper's CV backbone: stacked per-client conv weights route through
# kernels.grouped_conv instead of vmapping the round body.  Tiny shapes
# (16x16 images, width 8) keep compiles CI-sized; lr is small so fp32
# reassociation across the grouped rewrite stays far inside 1e-5.

RESNET_SIZES = (5, 9, 12, 20, 8, 16)        # ragged, 6 clients (mesh-padded)


@pytest.fixture(scope="module")
def resnet_setup():
    from repro.configs.paper import CIFAR10
    from repro.data.synthetic import SyntheticImageTask
    task = dataclasses.replace(CIFAR10, n_clients=len(RESNET_SIZES),
                               participation=1.0, batch_size=8, rounds=2,
                               local_epochs=1, image_hw=16, lr=0.01)
    gen = SyntheticImageTask(task.num_classes, hw=task.image_hw, seed=0)
    clients = [ClientData(*gen.generate(n, seed=100 + i))
               for i, n in enumerate(RESNET_SIZES)]
    tx, ty = gen.generate(64, seed=999)
    return task, FederatedData(clients, tx, ty,
                               np.zeros((len(RESNET_SIZES),
                                         task.num_classes)))


@pytest.mark.parametrize("name", ["fedavg", "fedgkd"])
def test_resnet8_vmap_matches_sequential(resnet_setup, name):
    """seq vs vmap on the conv backbone: the vmap executor must pick the
    client-batched body (telemetry) and reproduce the reference < 1e-5."""
    task, data = resnet_setup
    hs = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                               width=8, executor="sequential")
    hv = fl_loop.run_federated(task, algorithms.make(name), data, seed=0,
                               width=8, executor="vmap")
    assert hv.telemetry["round_body"] == "client_batched"
    assert _max_param_diff(hs.final_params, hv.final_params) < 1e-5
    for rs, rv in zip(hs.records, hv.records):
        assert abs(rs.mean_local_loss - rv.mean_local_loss) < 1e-5
        assert abs(rs.test_acc - rv.test_acc) < 1e-5


def test_resnet8_naive_body_still_available(resnet_setup):
    """client_batched=False forces the historical vmapped-conv body (the
    conv benchmark's baseline) and still matches the batched body."""
    task, data = resnet_setup
    hn = fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                               width=8, executor="vmap",
                               client_batched=False)
    hb = fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                               width=8, executor="vmap")
    assert hn.telemetry["round_body"] == "vmap"
    assert hb.telemetry["round_body"] == "client_batched"
    assert _max_param_diff(hn.final_params, hb.final_params) < 1e-5


def test_resnet8_async_inner_matches_sequential(resnet_setup):
    """Async degenerate regime with the vmap (client-batched) inner."""
    task, data = resnet_setup
    hs = fl_loop.run_federated(task, algorithms.make("fedgkd"), data, seed=0,
                               width=8, rounds=2, executor="sequential")
    ha = fl_loop.run_federated(
        task, algorithms.make("fedgkd"), data, seed=0, width=8, rounds=2,
        executor=ex.AsyncExecutor(staleness="constant", inner="vmap"))
    assert _max_param_diff(hs.final_params, ha.final_params) < 1e-5


def test_resnet8_auto_resolution():
    """'auto' now selects the batched route for conv backbones (closing
    the ROADMAP caveat) — but only when the algorithm has a stacked loss."""
    from repro.configs.paper import CIFAR10
    from repro.core.modelzoo import make_model
    model = make_model(CIFAR10)
    assert model.client_batched and not model.vmap_friendly
    assert ex.get_executor("auto", algorithms.make("fedavg"), 4,
                           model).name == "vmap"
    # moon overrides loss_fn without a stacked form -> sequential
    moon_model = make_model(CIFAR10, projection_head=True)
    assert ex.get_executor("auto", algorithms.make("moon"), 4,
                           moon_model).name == "sequential"


def test_round_context_client_batched_flag():
    from repro.configs.paper import CIFAR10, TOY
    from repro.core.modelzoo import make_model
    from repro.optim import sgd
    mk = lambda task, algo, cb: ex.RoundContext(
        algo=algorithms.make(algo), model=make_model(task), opt=sgd(),
        lr=0.1, batch_size=8, epochs=1, client_batched=cb)
    assert mk(CIFAR10, "fedavg", "auto").batched_local_update is not None
    assert mk(CIFAR10, "fedavg", False).batched_local_update is None
    assert mk(TOY, "fedavg", "auto").batched_local_update is None  # mlp
    assert mk(CIFAR10, "moon", "auto").batched_local_update is None
    with pytest.raises(ValueError, match="client_batched=True"):
        mk(TOY, "fedavg", True)


@multidevice
def test_resnet8_shard_map_strict_matches_sequential(resnet_setup):
    """K=6 ragged resnet8 cohort on the 8-device mesh, strict (no
    fallback): each shard trains its resident clients through the
    client-batched grouped-conv body; < 1e-5 vs sequential."""
    task, data = resnet_setup
    hs = fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                               width=8, executor="sequential")
    hm = fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                               width=8,
                               executor=ex.ShardMapExecutor(strict=True))
    assert hm.telemetry["route"] == "shard_map"
    assert hm.telemetry["round_body"] == "client_batched"
    assert _max_param_diff(hs.final_params, hm.final_params) < 1e-5


def test_resnet8_adam_matches_sequential(resnet_setup):
    """Adam's scalar step-count state leaf must stay per-client on the
    batched route (the opt init/update are vmapped) — regression for the
    keep-mask breaking on scalar optimizer state."""
    task, data = resnet_setup
    task = dataclasses.replace(task, optimizer="adam", lr=1e-3,
                               weight_decay=0.0)
    hs = fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                               width=8, rounds=1, executor="sequential")
    hv = fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                               width=8, rounds=1, executor="vmap")
    assert hv.telemetry["round_body"] == "client_batched"
    assert _max_param_diff(hs.final_params, hv.final_params) < 1e-5


def test_batched_loss_guard_on_loss_override():
    """A subclass overriding loss_fn WITHOUT a stacked form must not
    inherit the parent's batched loss (it would silently train the wrong
    objective on the batched route)."""
    from repro.configs.paper import CIFAR10
    from repro.core.modelzoo import make_model
    model = make_model(CIFAR10)

    class CustomGKD(algorithms.FedGKD):
        def loss_fn(self, m):
            return super().loss_fn(m)

    class CustomProx(algorithms.FedProx):
        def loss_fn(self, m):
            return super().loss_fn(m)

    assert CustomGKD().batched_loss_fn(model) is None
    assert CustomProx().batched_loss_fn(model) is None
    # inheriting BOTH unchanged keeps the batched form (fedgkd+)
    ph = make_model(CIFAR10, projection_head=True)
    assert algorithms.FedGKDPlus().batched_loss_fn(ph) is not None
