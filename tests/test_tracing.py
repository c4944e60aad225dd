"""The program's recorder of spans, counters and compiles
(``repro.core.tracing``) on the path the benchmark's cells run: the vmap
executor's round, the server's teacher buffer and ``evaluate``, on the
TOY mlp task.  Also pins the jitted programs' names that the benchmark's
device-trace metrics read (``jit_local_update``, ``jit_stage``)."""
import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper import CIFAR10, TOY
from repro.core import algorithms, executor, fl_loop, tracing
from repro.data.pipeline import ClientData, FederatedData
from repro.data.synthetic import SyntheticTabularTask
from repro.optim import sgd

SIZES = (20, 45, 64, 100, 130, 150)
N_TEST = 300
EVAL_BATCH = 256                     # fl_loop.evaluate's default


def _data(task):
    gen = SyntheticTabularTask(task.num_classes, dim=task.feat_dim, seed=0)
    clients = [ClientData(*gen.generate(n, seed=100 + i))
               for i, n in enumerate(SIZES)]
    test_x, test_y = gen.generate(N_TEST, seed=999)
    return FederatedData(clients, test_x, test_y,
                         np.zeros((len(SIZES), task.num_classes)))


def _task(participation):
    return dataclasses.replace(TOY, n_clients=len(SIZES),
                               participation=participation, batch_size=32,
                               rounds=2, local_epochs=1)


@pytest.fixture
def recorder():
    rec = tracing.enable()
    try:
        yield rec
    finally:
        tracing.disable()


@pytest.fixture(scope="module")
def fedgkd_run():
    """Two fedgkd rounds on the vmap executor, half the clients a round,
    with the recorder on."""
    task = _task(0.5)
    rec = tracing.enable()
    try:
        h = fl_loop.run_federated(task, algorithms.make("fedgkd"),
                                  _data(task), seed=0, executor="vmap")
    finally:
        tracing.disable()
    return rec, h


def test_off_records_nothing_and_shares_one_null_context():
    rec = tracing.enable()
    tracing.disable()
    assert tracing.span("fl.a") is tracing.span("fl.b")
    assert isinstance(tracing.span("fl.a"), contextlib.nullcontext)
    assert tracing.round_span(1) is tracing.span("fl.a")
    task = _task(0.5)
    fl_loop.run_federated(dataclasses.replace(task, rounds=1),
                          algorithms.make("fedgkd"), _data(task), seed=0,
                          executor="vmap")
    tracing.count("host_syncs")
    assert not rec.spans and not rec.counters and not rec.functions


def test_span_tree(fedgkd_run):
    rec, _ = fedgkd_run
    spans = rec.spans
    for r in (1, 2):
        rounds = [i for i, s in enumerate(spans)
                  if s.name == tracing.ROUND and s.round == r]
        assert len(rounds) == 1 and spans[rounds[0]].parent is None
        calls = [i for i, s in enumerate(spans)
                 if s.name == "fl.run_round" and s.round == r]
        assert len(calls) == 1 and spans[calls[0]].parent == rounds[0]
        inner = {s.name for s in spans
                 if s.parent == calls[0] and s.round == r}
        assert inner == {"fl.exec.full_shards", "fl.exec.precompute",
                         "fl.exec.picks", "fl.exec.stack", "fl.exec.body",
                         "fl.exec.losses"}
        assert all(s.name.startswith("fl.exec.") == (s.parent == calls[0])
                   for s in spans if s.round == r)
        update = next(i for i, s in enumerate(spans)
                      if s.name == "fl.server_update" and s.round == r)
        assert spans[update].parent == rounds[0]
        push = next(s for s in spans
                    if s.name == "fl.server.push" and s.round == r)
        assert push.parent == update
        ev = next(s for s in spans if s.name == "fl.evaluate"
                  and s.round == r)
        assert ev.parent == rounds[0]
    # outside any round: the first push (init_server) and the last
    # local-model evaluate
    assert {s.name for s in spans if s.round == 0} == {
        "fl.server.push", "fl.evaluate"}
    assert all(s.t0 <= s.t1 for s in spans)


def test_self_seconds_is_duration_less_children(fedgkd_run):
    rec, _ = fedgkd_run
    for r in (1, 2):
        for name in (tracing.ROUND, "fl.run_round", "fl.server_update",
                     "fl.exec.stack"):
            idx = [i for i, s in enumerate(rec.spans)
                   if s.name == name and s.round == r]
            children = sum(s.seconds for s in rec.spans if s.parent in idx)
            total = sum(rec.spans[i].seconds for i in idx)
            assert rec.per_round(name, [r]) == [pytest.approx(total)]
            assert rec.self_seconds(name, [r]) == [
                pytest.approx(total - children)]
            assert 0 <= rec.self_seconds(name, [r])[0] <= total
    assert rec.self_seconds("fl.exec.stack", [1]) == rec.per_round(
        "fl.exec.stack", [1])
    assert rec.per_round("fl.no.such.span", [1, 2]) == [0.0, 0.0]


def test_precompute_rows_are_the_padded_and_the_real_shards(fedgkd_run):
    rec, h = fedgkd_run
    for rnd in h.records:
        n = [SIZES[c] for c in rnd.sampled]
        assert rec.counter("precompute_rows", [rnd.round]) == [
            len(n) * max(n)]
        assert rec.counter("precompute_rows_real", [rnd.round]) == [sum(n)]


def _inexact_leaves(params) -> int:
    return sum(1 for leaf in jax.tree_util.tree_leaves(params)
               if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact))


@pytest.mark.parametrize("algo", ["fedavg", "fedgkd"])
def test_host_syncs_are_the_reads_each_site_makes(algo, recorder):
    """A round reads the losses once and ``evaluate`` its per-batch
    results once, whatever the number of test batches; FedGKD's teacher
    buffer also checks each inexact leaf of the new global for non-finite
    values, then reads one comparison to find it differs from the
    buffer's head."""
    task = _task(0.5)
    h = fl_loop.run_federated(task, algorithms.make(algo), _data(task),
                              seed=0, executor="vmap")
    assert math.ceil(N_TEST / EVAL_BATCH) > 1
    per_round = 1 + 1
    if algo == "fedgkd":
        per_round += _inexact_leaves(h.final_params) + 1
    assert recorder.counter("host_syncs", [1, 2]) == [per_round] * 2


def test_eval_uploads_the_test_set_once(recorder):
    """The first round uploads the test set, later rounds and the final
    local-model evaluation reuse it; another host array uploads again,
    and the resident copy goes with the host array."""
    task = _task(0.5)
    data = _data(task)
    fl_loop.run_federated(task, algorithms.make("fedavg"), data, seed=0,
                          executor="vmap")
    assert recorder.counter("eval_uploads", [1, 2]) == [1, 0]
    assert recorder.counter("eval_uploads", [0]) == [0]
    model = fl_loop.make_model(task)
    params = model.init(jax.random.PRNGKey(0))
    other_x = data.test_x.copy()
    first = fl_loop.evaluate(model, params, data.test_x, data.test_y)
    assert recorder.counter("eval_uploads", [0]) == [0]
    assert fl_loop.evaluate(model, params, other_x, data.test_y) == first
    assert recorder.counter("eval_uploads", [0]) == [1]
    keys = [k for k in fl_loop._TEST_SETS if k[0] == id(other_x)]
    assert len(keys) == 1
    del other_x
    assert keys[0] not in fl_loop._TEST_SETS


def test_resident_test_sets_are_a_bounded_lru(recorder):
    """At most ``_TEST_SETS_MAX`` test sets stay on the device; the least
    recently evaluated goes first."""
    task = _task(0.5)
    data = _data(task)
    model = fl_loop.make_model(task)
    params = model.init(jax.random.PRNGKey(0))
    xs = [data.test_x.copy() for _ in range(fl_loop._TEST_SETS_MAX + 1)]
    for x in xs:
        fl_loop.evaluate(model, params, x, data.test_y)
    fl_loop.evaluate(model, params, xs[1], data.test_y)
    assert recorder.counter("eval_uploads", [0]) == [len(xs)]
    held = {k[0] for k in fl_loop._TEST_SETS}
    assert id(xs[0]) not in held
    assert {id(x) for x in xs[1:]} <= held
    fl_loop.evaluate(model, params, xs[1], data.test_y)
    assert recorder.counter("eval_uploads", [0]) == [len(xs)]


def test_only_new_shapes_trace(recorder):
    """Every client every round: the second round has the first's shapes
    and traces, lowers and compiles nothing; the first traced the round
    body, the precompute and its gather, and compiled each once."""
    task = _task(1.0)
    fl_loop.run_federated(task, algorithms.make("fedgkd"), _data(task),
                          seed=0, executor="vmap")
    first = recorder.compiles_by_function([1])
    for fun in ("local_update", "stage", "precompute_gather"):
        assert first[fun]["traces"] == 1 and first[fun]["compiles"] == 1
        assert first[fun]["seconds"] > 0
    assert recorder.compiles_by_function([2]) == {}
    assert recorder.compile_seconds([2]) == [0.0]
    assert 0 < recorder.compile_seconds([1])[0] <= sum(
        t["seconds"] for t in first.values())


def test_compile_seconds_is_the_union_of_nested_events():
    rec = tracing.Recorder()
    rec.compile_spans[3] = [(1.0, 2.0), (1.2, 1.5), (1.8, 2.5), (4.0, 4.5)]
    assert rec.compile_seconds([3, 4]) == [pytest.approx(2.0), 0.0]


def test_round_charges_spans_counters_and_compiles():
    rec = tracing.enable()
    try:
        with tracing.round_span(7):
            with tracing.span("fl.a"):
                tracing.count("n", 2)
                jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))
        tracing.count("n")
    finally:
        tracing.disable()
    assert [(s.name, s.round) for s in rec.spans] == [
        (tracing.ROUND, 7), ("fl.a", 7)]
    assert rec.counter("n", [0, 7]) == [1, 2]
    tally = rec.compiles_by_function([7])["<lambda>"]
    assert (tally["traces"], tally["lowerings"], tally["compiles"]) == (
        1, 1, 1)


# -- stable program names ------------------------------------------------

def _lowered(task, algo_name, feat, k=2, n=16, s=2, b=4):
    """Lower the vmap executor's round body, teacher precompute and gather
    at abstract shapes."""
    algo = algorithms.make(algo_name)
    model = fl_loop.make_model(task, algo.needs_projection_head, width=16)
    ctx = executor.RoundContext(algo=algo, model=model, opt=sgd(), lr=0.1,
                                batch_size=b, epochs=1)
    params = model.init(jax.random.PRNGKey(0))
    server = algo.init_server(params, model, task.num_classes)
    payload = algo.round_payload(server, jax.random.PRNGKey(1))
    sds = jax.ShapeDtypeStruct
    full = (sds((k, n) + feat, jnp.float32), sds((k, n), jnp.int32),
            sds((k, n), jnp.float32))
    picks = sds((k, s, b), jnp.int32)
    vx = executor.VmapExecutor()
    stage, gather = vx._precompute_fn(ctx), vx._gather_fn(ctx)
    aux_full = jax.eval_shape(stage, payload, *full)
    aux = jax.eval_shape(gather, aux_full, picks)
    states = executor.tree_stack([algo.init_client_state(i, params)
                                  for i in range(k)])
    body = vx._round_fn(ctx).lower(
        params, payload, states, sds((k, s, b) + feat, jnp.float32),
        sds((k, s, b), jnp.int32), sds((k, s, b), jnp.float32), aux,
        sds((k, s), jnp.bool_), ctx.lr)
    return ctx, {"body": body.as_text(),
                 "stage": stage.lower(payload, *full).as_text(),
                 "gather": gather.lower(aux_full, picks).as_text()}


@pytest.mark.parametrize("task,feat,round_body", [
    (TOY, (TOY.feat_dim,), "vmap"),
    (CIFAR10, (32, 32, 3), "client_batched")], ids=["mlp", "resnet8"])
def test_jitted_program_names_are_stable(task, feat, round_body):
    """``round_body_device_ms`` reads ``jit_local_update`` and
    ``precompute_device_ms`` reads ``jit_stage`` on the device trace:
    renaming either program silently nulls its metric."""
    ctx, text = _lowered(task, "fedgkd", feat)
    assert (ctx.batched_local_update is not None) == (
        round_body == "client_batched")
    assert "module @jit_local_update" in text["body"]
    assert "module @jit_stage" in text["stage"]
    assert "module @jit_precompute_gather" in text["gather"]
