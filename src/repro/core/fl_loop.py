"""Algorithm-agnostic federated training loop (Alg. 1 ServerExecution).

Single-host simulation path used by the paper-reproduction benchmarks.  HOW
the sampled clients run each round is delegated to a pluggable
``ClientExecutor`` (repro.core.executor): sequential reference, batched
vmap (one jitted call trains the whole cohort), or the multi-device
shard_map route (cohort sharded over a ("clients",) mesh with
device-resident client shards).  The multi-device driver for the big
assigned architectures lives in repro/launch/train.py.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import weakref
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper import PaperTask
from repro.core import executor as executor_lib, tracing
from repro.core.algorithms import Algorithm, FedGen
from repro.core.distillation import cross_entropy
from repro.core.modelzoo import ModelBundle, make_model
from repro.data.pipeline import FederatedData, num_batches as \
    pipeline_num_batches
from repro.optim import adam, sgd


@dataclasses.dataclass
class RoundRecord:
    round: int
    test_acc: float
    test_loss: float
    mean_local_loss: float
    seconds: float
    # -- async extensions (defaults keep synchronous records unchanged) --
    sim_time: float = 0.0        # virtual clock at this aggregation event
    version: int = 0             # global model version AFTER the update
    mean_staleness: float = 0.0  # mean (version - start_version) in buffer
    sampled: tuple = ()          # client ids aggregated this round (sync:
    #                              the sampled cohort) — benchmarks replay
    #                              simulated wall-clock from these


@dataclasses.dataclass
class History:
    algo: str
    records: list[RoundRecord]
    final_params: Any
    local_model_acc: float = 0.0       # last sampled client's local-model acc
    telemetry: dict = dataclasses.field(default_factory=dict)
    #                                  # final RoundContext.telemetry snapshot

    @property
    def best_acc(self) -> float:
        return max(r.test_acc for r in self.records)

    @property
    def final_acc(self) -> float:
        return self.records[-1].test_acc

    def accs(self) -> list[float]:
        return [r.test_acc for r in self.records]


# evaluate() is called every round for every run.  One jitted program per
# distinct apply fn evaluates the whole test set (bundles built for the
# same backbone share it; jax retraces per params/test-set shape
# underneath as usual).  Bounded FIFO: the distilbert bundle creates a
# fresh apply closure per make_model, so an unbounded dict would leak
# compiled executables across sweep runs (and the jitted value strongly
# references its key, ruling out weakrefs).
_APPLY_CACHE: "collections.OrderedDict[Callable, Callable]" = \
    collections.OrderedDict()
_APPLY_CACHE_MAX = 32

# The test set as the evaluation program reads it, uploaded once per host
# pair (x, y) and batch size: [n_batches, batch, ...], the last batch
# padded with zero inputs under the ignore label, which masks the padded
# rows out of both the loss and the count of correct predictions.  Keyed
# on the host arrays' identity (the arrays are taken as read-only); an
# entry goes when either array does, and the LRU bound keeps sweeps from
# piling up device copies.  Uncommitted (no device named), so params
# placed on a mesh still evaluate.
_TEST_SETS: "collections.OrderedDict[tuple, tuple]" = \
    collections.OrderedDict()
_TEST_SETS_MAX = 4
_IGNORE_INDEX = -1           # distillation.cross_entropy's default


def _eval_program(model: ModelBundle) -> Callable:
    """(params, xs, ys) -> per batch (correct predictions, mean loss)."""
    fn = _APPLY_CACHE.get(model.apply)
    if fn is None:
        apply = model.apply

        def run(params, xs, ys):
            def one(batch):
                xb, yb = batch
                logits = apply(params, xb)
                correct = jnp.sum(jnp.argmax(logits, axis=-1) == yb)
                return correct, cross_entropy(logits, yb, _IGNORE_INDEX)
            return jax.lax.map(one, (xs, ys))

        fn = jax.jit(run)
        _APPLY_CACHE[model.apply] = fn
        while len(_APPLY_CACHE) > _APPLY_CACHE_MAX:
            _APPLY_CACHE.popitem(last=False)
    return fn


def _resident_test_set(x, y, batch: int) -> tuple:
    key = (id(x), id(y), batch)
    hit = _TEST_SETS.get(key)
    if hit is not None and hit[0]() is x and hit[1]() is y:
        _TEST_SETS.move_to_end(key)
        return hit[2]
    n = len(y)
    rows = -(-n // batch) * batch
    xs = np.zeros((rows,) + x.shape[1:], x.dtype)
    ys = np.full((rows,) + y.shape[1:], _IGNORE_INDEX, y.dtype)
    xs[:n], ys[:n] = x, y
    stacked = (jnp.asarray(xs.reshape((-1, batch) + xs.shape[1:])),
               jnp.asarray(ys.reshape((-1, batch) + ys.shape[1:])))
    tracing.count("eval_uploads")

    def drop(_ref):
        _TEST_SETS.pop(key, None)

    _TEST_SETS[key] = (weakref.ref(x, drop), weakref.ref(y, drop), stacked)
    while len(_TEST_SETS) > _TEST_SETS_MAX:
        _TEST_SETS.popitem(last=False)
    return stacked


def evaluate(model: ModelBundle, params: Any, x: np.ndarray, y: np.ndarray,
             batch: int = 256) -> tuple[float, float]:
    """Accuracy and mean cross-entropy of ``model`` over the test set, in
    batches of ``batch`` rows: one device program over the resident copy
    of ``(x, y)`` and one host read."""
    with tracing.span("fl.evaluate"):
        xs, ys = _resident_test_set(x, y, batch)
        correct, losses = jax.device_get(_eval_program(model)(params, xs, ys))
    tracing.count("host_syncs")
    n = len(y)
    rows = np.full(len(losses), batch, np.float64)
    rows[-1] = n - batch * (len(losses) - 1)
    acc = int(np.sum(correct, dtype=np.int64)) / np.size(y)
    return acc, float(np.sum(losses.astype(np.float64) * rows) / n)


def run_federated(task: PaperTask, algo: Algorithm,
                  data: Optional[FederatedData] = None, *,
                  population=None,
                  rounds: Optional[int] = None, seed: int = 0,
                  eval_every: int = 1, max_batches_per_client: int | None = None,
                  verbose: bool = False, width: int = 16,
                  round_callback=None, dp=None,
                  executor: "str | executor_lib.ClientExecutor" = "auto",
                  precompute: "bool | str" = "auto",
                  client_batched: "bool | str" = "auto",
                  faults=None, fault_policy=None,
                  checkpoint_dir: Optional[str] = None,
                  checkpoint_every: int = 1,
                  resume: bool = False) -> History:
    """Run T communication rounds of ``algo`` on the partitioned data.

    ``data`` is the eager in-memory dataset (``FederatedData``); for
    large populations pass ``population=`` (a ``repro.population.
    Population``) instead — clients then materialize lazily through the
    cold/warm/hot tiers, cohorts come from the hierarchical O(cohort)
    sampler (``n_shards=1`` reproduces the flat ``rng.choice`` sequence
    bit-identically), per-client algorithm state moves into the same
    tiers, and tier hit/miss/eviction counters surface on
    ``History.telemetry["population"]``.

    ``executor`` selects the client-execution strategy: ``"sequential"``,
    ``"vmap"``, ``"shard_map"``, ``"async"`` (buffered straggler-aware
    rounds on a simulated heterogeneous system — see ``_run_async`` and
    ``executor_lib.AsyncExecutor`` for the knobs; records then carry
    ``sim_time``/``version``/``mean_staleness``), an executor instance, or
    ``"auto"`` (batched vmap whenever the algorithm supports it).
    ``precompute``
    gates the round-level teacher-precompute stage (the algorithm's
    ``precompute_aux`` hook): ``"auto"`` enables it for the batched
    executors only — on the sequential reference the per-client dispatch
    and host round-trips cost more than the hoisted teacher forward saves
    (see BENCH_executor.json) — while ``True``/``False`` force it; False
    is the inline no-aux pre-pipeline path, kept for equivalence tests
    and benchmarking.  ``client_batched`` gates the batched executors'
    client-batched round body on conv backbones (``"auto"`` uses it when
    the model + algorithm support it; ``False`` forces the historical
    vmapped body — the conv benchmarks' naive baseline).

    ``faults=`` (a ``systemsim.FaultProfile``) turns on fault-tolerant
    rounds: per-dispatch crash/timeout/corrupt draws from a dedicated
    child stream of the seed (identical across executor routes), a
    server-side ``validate_update`` admission gate, quorum aggregation
    with capped-exponential-backoff retries (``fault_policy=``, a
    ``server.FaultPolicy``), and fault counters on
    ``History.telemetry["faults"]``.  A zero-probability profile is
    bit-identical to ``faults=None``.

    ``checkpoint_dir=`` persists the FULL run state every
    ``checkpoint_every`` rounds (params, teacher buffer, rng/sampler
    state, per-client state, round records — ``checkpoint.recovery``);
    ``resume=True`` restores the newest loadable state file from that
    directory (torn files are skipped) and continues bit-identically to
    the uninterrupted run.  This composes with ``executor="async"`` (the
    in-flight event heap, its tagged upload pytrees and the per-client
    retry state serialize with the rest — a kill mid-wave resumes the
    exact wave) and with ``population=`` (the per-client state tiers
    snapshot their warm entries by value and their spill set by
    reference, so resume re-warms lazily from the same spill files;
    stateless algorithms re-init bit-identically and snapshot nothing).
    """
    if (data is None) == (population is None):
        raise ValueError("pass exactly one of data= (eager FederatedData) "
                         "or population= (repro.population.Population)")
    pop = population
    if pop is not None:
        data = pop      # duck-typed: clients[cid] / test_x / sample_cohort
    multihost = pop is not None and getattr(pop, "multihost", False)
    rounds = rounds if rounds is not None else task.rounds
    model = make_model(task, projection_head=algo.needs_projection_head,
                       width=width)
    rng = np.random.default_rng(seed)
    jrng = jax.random.PRNGKey(seed)

    global_params = model.init(jax.random.PRNGKey(seed + 1))
    # multi-host: probe shapes from the cold source directly — a host that
    # does not own client 0 must not pull it into its warm tier
    probe_x = jnp.asarray((pop.probe_client() if multihost
                           else data.clients[0]).x[:2])
    if isinstance(algo, FedGen):
        server = algo.init_server_with_probe(global_params, model,
                                             task.num_classes, probe_x)
    else:
        server = algo.init_server(global_params, model, task.num_classes)

    if rounds == 0:      # empty-history fast path (no uploads, no eval)
        return History(algo.name, [], server["global"], 0.0)

    if task.optimizer == "adam":
        opt = adam(weight_decay=task.weight_decay)
    else:
        opt = sgd(momentum=task.momentum, weight_decay=task.weight_decay)

    n_sample = max(1, int(round(task.participation * data.n_clients)))
    exec_ = executor_lib.get_executor(executor, algo, n_sample, model)
    inner = None
    if isinstance(exec_, executor_lib.AsyncExecutor):
        inner = exec_.resolve_inner(algo, n_sample, model)
    if precompute == "auto":
        effective = inner.name if inner is not None else exec_.name
        precompute = effective != "sequential"
    ctx = executor_lib.RoundContext(
        algo=algo, model=model, opt=opt, lr=task.lr,
        batch_size=task.batch_size, epochs=task.local_epochs,
        max_batches=max_batches_per_client, precompute=bool(precompute),
        client_batched=client_batched)

    if multihost:
        if dp is not None:
            raise NotImplementedError(
                "multi-host placement does not compose with dp= yet")
        # this host's devices must never materialize an unowned slab
        ctx.placement.owns = pop.owned

    if pop is not None:
        # hot tier coherence: warm evictions drop device slabs, slab-store
        # evictions feed population telemetry, pinned set shared
        pop.attach_hot(ctx.placement)
        # lazy per-client state, same tiers (the eager dict below is
        # O(population) host memory — a model copy per client for
        # moon-style states)
        client_states = pop.make_client_states(algo, global_params)
    else:
        client_states = {k: algo.init_client_state(k, global_params)
                         for k in range(data.n_clients)}
    # small server-side validation split for FedGKD-VOTE coefficients
    n_val = min(256, len(data.test_y) // 4)
    val_batch = (jnp.asarray(data.test_x[:n_val]), jnp.asarray(data.test_y[:n_val]))

    injector = None
    policy = None
    if faults is not None:
        from repro.core import systemsim
        from repro.core.server import FaultPolicy
        injector = systemsim.FaultInjector(faults,
                                           systemsim.derive_fault_rng(seed))
        policy = fault_policy if fault_policy is not None else FaultPolicy()
        ctx.telemetry["faults"] = _fault_counters(policy)

    if resume and checkpoint_dir is None:
        raise ValueError("resume=True requires checkpoint_dir=")

    if inner is not None:
        return _run_async(task, algo, data, model, server, ctx, exec_, inner,
                          rng, jrng, seed=seed, rounds=rounds,
                          eval_every=eval_every, verbose=verbose,
                          round_callback=round_callback, dp=dp,
                          n_sample=n_sample, client_states=client_states,
                          val_batch=val_batch, pop=pop,
                          injector=injector, policy=policy,
                          checkpoint_dir=checkpoint_dir,
                          checkpoint_every=checkpoint_every, resume=resume)

    records: list[RoundRecord] = []
    local_acc = 0.0
    uploads: list[dict] = []
    ckpt_host = pop.placement.host_id if multihost else None
    dead_hosts: set = set()     # peers that missed an exchange deadline

    start_round = 0
    if resume:
        from repro.checkpoint import recovery
        hit = recovery.load_latest_state(checkpoint_dir, host=ckpt_host)
        if multihost:
            # coordinated resume: agree on the newest round EVERY host can
            # load (min over hosts — a host that checkpointed further
            # ahead still has the earlier file), restore it, retire this
            # host's stale wave/round exchange files, then confirm all
            # hosts restored the same state before the first round
            from repro.population import placement as placement_lib
            common = placement_lib.resume_barrier(
                pop.placement, hit[2] if hit is not None else None)
            if common is None:
                hit = None
            elif hit is None or hit[2] != common:
                hit = (*recovery.load_state_at(checkpoint_dir, common,
                                               host=ckpt_host), common)
        if hit is not None:
            state, meta, start_round = hit
            if meta.get("algo") not in (None, algo.name):
                raise ValueError(
                    f"resume: checkpoint was written by algo "
                    f"{meta.get('algo')!r}, this run is {algo.name!r}")
            server = state["server"]
            jrng = state["jrng"]
            recovery.restore_rng(rng, state["np_rng"])
            if injector is not None and state.get("fault_rng") is not None:
                recovery.restore_rng(injector.rng, state["fault_rng"])
                if state.get("fault_counters") is not None:
                    injector.counters.update(state["fault_counters"])
                if state.get("fault_telemetry") is not None:
                    ctx.telemetry["faults"].update(state["fault_telemetry"])
            records = [RoundRecord(**d) for d in state["records"]]
            _restore_client_states(client_states, state["client_states"])
        if multihost:
            placement_lib.clear_host_payloads(pop.placement)
            placement_lib.confirm_resume(
                pop.placement, None if hit is None else start_round,
                {"round": None if hit is None else start_round,
                 "algo": algo.name})

    for t in range(start_round, rounds):
        with tracing.round_span(t + 1):
            t0 = time.perf_counter()
            jrng, krng = jax.random.split(jrng)
            sampled = data.sample_cohort(rng, n_sample)
            payload = algo.round_payload(server, krng)

            cids = [int(k) for k in sampled]
            if multihost and injector is not None:
                # placement-aware fault tolerance: a crashed/deadline-
                # missing HOST is a correlated fault over its whole owned
                # slice; quorum counts surviving hosts' validated uploads
                # and retry re-dispatches the absent slice through the
                # exchange
                uploads, weights, local_losses = _multihost_fault_round(
                    exec_, ctx, pop, server, payload, client_states, rng,
                    cids, injector, policy, t, dead_hosts)
            elif multihost:
                # train only the owned slice, exchange uploads, aggregate
                # the identical full-cohort update on every host
                uploads, weights, local_losses = _multihost_round(
                    ctx, exec_, pop, server["global"], payload,
                    client_states, cids, rng, t)
            elif injector is None:
                if pop is not None:
                    # the cohort must not thrash the warm tier against
                    # itself while the round materializes / trains it
                    pop.pin(cids)
                with tracing.span("fl.run_round"):
                    result = exec_.run_round(
                        ctx, server["global"], payload,
                        [client_states[k] for k in cids],
                        [data.clients[k] for k in cids], rng,
                        client_ids=cids)
                uploads, weights = result.uploads, result.weights
                local_losses = result.local_losses
                for k, new_state in zip(cids, result.client_states):
                    client_states[k] = new_state
            else:
                if pop is not None:
                    pop.pin(cids)
                uploads, weights, local_losses = _fault_tolerant_round(
                    exec_, ctx, server, payload, client_states, data, rng,
                    cids, injector, policy)
            if verbose and t == 0:
                # which route actually ran (the shard_map executor may
                # degrade to vmap on a single device — see
                # RoundContext.telemetry)
                tele = ctx.telemetry
                print(f"[{algo.name}] executor route: "
                      f"{tele.get('route', exec_.name)}"
                      + (f" ({tele['n_devices']} devices, cohort "
                         f"{tele['cohort']} padded to {tele['padded_to']})"
                         if "padded_to" in tele else ""))
            if pop is not None and not multihost:
                pop.unpin(cids)
                ctx.telemetry["population"] = pop.stats()

            if not uploads:
                # every client of the cohort crashed/was rejected through
                # all retries: hold the global fixed rather than aggregate
                # nothing
                ctx.telemetry["faults"]["skipped_rounds"] += 1
            else:
                if dp is not None:
                    from repro.core import privacy
                    uploads = privacy.privatize_uploads(
                        uploads, server["global"], dp, t)
                with tracing.span("fl.server_update"):
                    server = algo.server_update(server, uploads, weights,
                                                model, val_batch,
                                                n_clients=data.n_clients)
                if dp is not None:
                    from repro.core import privacy
                    server["global"] = privacy.noise_aggregate(
                        server["global"], dp, len(uploads), t)

            if (t + 1) % eval_every == 0 or t == rounds - 1:
                acc, loss = evaluate(model, server["global"], data.test_x,
                                     data.test_y)
            elif records:
                acc, loss = records[-1].test_acc, records[-1].test_loss
            else:
                acc, loss = 0.0, 0.0
            records.append(RoundRecord(
                t + 1, acc, loss,
                float(np.mean(local_losses)) if local_losses else 0.0,
                time.perf_counter() - t0,
                sampled=tuple(int(k) for k in sampled)))
            if checkpoint_dir is not None and (
                    (t + 1) % checkpoint_every == 0 or t == rounds - 1):
                _save_checkpoint(checkpoint_dir, t + 1, algo, server, jrng,
                                 rng, injector, records, client_states,
                                 data.n_clients,
                                 ftel=ctx.telemetry.get("faults"),
                                 host=ckpt_host)
            if round_callback is not None:
                round_callback(t + 1, server, model)
            if verbose:
                print(f"[{algo.name}] round {t+1:3d}/{rounds} "
                      f"acc={acc:.4f} loss={loss:.4f} "
                      f"local={np.mean(local_losses):.4f}")

    # paper Fig.2-style: accuracy of the last trained LOCAL model
    if uploads:
        local_acc, _ = evaluate(model, uploads[-1]["params"],
                                data.test_x, data.test_y)
    if injector is not None:
        ctx.telemetry["faults"].update(injector.counters)
    return History(algo.name, records, server["global"], local_acc,
                   dict(ctx.telemetry))


class _SizeOnly:
    """``materialize_picks`` touches only ``.n`` — this stub lets every
    host pre-draw the full cohort's batch indices from client sizes alone
    (``client_n`` never materializes arrays), keeping the numpy stream in
    lockstep across hosts."""

    __slots__ = ("n",)

    def __init__(self, n: int):
        self.n = int(n)


def _multihost_round(ctx, exec_, pop, global_params, payload, client_states,
                     cids, rng, t):
    """One synchronous round under multi-host placement.

    Every host arrives here with identical ``rng``/``payload``/``cids``
    (the sampler draws in lockstep).  Each host pre-draws batch picks for
    the WHOLE cohort in cohort order — consuming the generator exactly as
    the single-host executors would — then trains only the slice it owns,
    publishes its uploads through the filesystem allgather, and rebuilds
    the full cohort-ordered upload list from every host's payload
    (including its own, re-read from its file, so all hosts aggregate
    byte-identical inputs).  Per-host tier telemetry lands on
    ``telemetry["population"]["hosts"]``, indexed by host id.
    """
    from repro.population import placement as placement_lib

    own_idx = [i for i, c in enumerate(cids) if pop.owned(c)]
    own_cids = [cids[i] for i in own_idx]
    picks_all = [executor_lib.materialize_picks(
        rng, _SizeOnly(pop.client_n(c)), ctx.batch_size, ctx.epochs,
        ctx.max_batches) for c in cids]
    if own_cids:
        pop.pin(own_cids)
        result = exec_.run_round(
            ctx, global_params, payload,
            [client_states[k] for k in own_cids],
            [pop.clients[k] for k in own_cids], rng,
            client_ids=own_cids, picks=[picks_all[i] for i in own_idx])
        pop.unpin(own_cids)
        for k, new_state in zip(own_cids, result.client_states):
            client_states[k] = new_state
        local = {"idx": own_idx, "uploads": result.uploads,
                 "weights": [float(w) for w in result.weights],
                 "losses": [float(v) for v in result.local_losses]}
    else:                       # this host owns nobody this round: it still
        local = {"idx": [], "uploads": [],  # publishes (the barrier) and
                 "weights": [], "losses": []}  # aggregates like the rest
    local["stats"] = dict(pop.stats(),
                          host_rss_mb=placement_lib.peak_rss_mb(),
                          slab=ctx.placement.stats(),
                          exchange=dict(pop.placement.stats))
    gathered = placement_lib.allgather(pop.placement, f"round{t:06d}", local)
    k = len(cids)
    uploads: list = [None] * k
    weights = [0.0] * k
    losses = [0.0] * k
    for host_payload in gathered:
        for j, i in enumerate(host_payload["idx"]):
            uploads[int(i)] = host_payload["uploads"][j]
            weights[int(i)] = float(host_payload["weights"][j])
            losses[int(i)] = float(host_payload["losses"][j])
    missing = [cids[i] for i, u in enumerate(uploads) if u is None]
    if missing:
        raise RuntimeError(
            f"multi-host round {t}: no host owned clients "
            f"{missing[:5]}{'...' if len(missing) > 5 else ''} — the "
            f"placement does not partition the cohort")
    ctx.telemetry["population"] = dict(
        pop.stats(), hosts=[g["stats"] for g in gathered])
    return uploads, weights, losses


def _fault_counters(policy) -> dict:
    """Zeroed ``History.telemetry["faults"]`` schema (injection counters
    from ``FaultInjector.counters`` merge in at the end of the run)."""
    return {"crashes": 0, "timeouts": 0, "corrupt_injected": 0,
            "rejected_nonfinite": 0, "rejected_norm": 0,
            "retries": 0, "redispatches": 0, "backoff_wait": 0.0,
            "quorum_shortfalls": 0, "skipped_rounds": 0,
            "dropped_clients": 0, "quorum_frac": policy.quorum_frac,
            # multi-host placement: injected host crashes (from the
            # injector counters) and real exchange-deadline misses
            "host_crashes": 0, "host_timeouts": 0}


def _fault_tolerant_round(exec_, ctx, server, payload, client_states, data,
                          rng, cids, injector, policy):
    """One synchronous round under fault injection: train the cohort, draw
    per-dispatch faults, gate survivors through ``validate_update``, and
    retry the failed subset with capped exponential backoff until
    ``quorum_frac`` of the cohort survives (or retries run out).

    Returns ``(uploads, weights, local_losses)`` over the survivors, in
    cohort order; survivor client state commits, failed state does not (a
    crashed client's local work is lost, a corrupt client's state is as
    suspect as its update).  With a zero-probability profile every client
    survives on attempt 0 and the round is bit-identical to the unfaulted
    path.
    """
    from repro.core import systemsim
    from repro.core.server import validate_update

    ftel = ctx.telemetry["faults"]
    quorum = max(1, int(np.ceil(policy.quorum_frac * len(cids))))
    uploads: list[dict] = []
    weights: list[float] = []
    losses: list[float] = []
    state_commits: dict = {}
    pending = list(cids)
    attempt = 0
    while pending:
        drawn = [(k, injector.draw()) for k in pending]
        # crash/timeout: the update never arrives, nothing to train for —
        # the simulation skips the wasted local work entirely
        failed = [k for k, f in drawn
                  if f is not None and f[0] in ("crash", "timeout")]
        alive = [(k, f) for k, f in drawn
                 if f is None or f[0] == "corrupt"]
        if alive:
            ids = [k for k, _ in alive]
            result = exec_.run_round(
                ctx, server["global"], payload,
                [client_states[k] for k in ids],
                [data.clients[k] for k in ids], rng, client_ids=ids)
            for i, (k, f) in enumerate(alive):
                up = result.uploads[i]
                if f is not None:
                    up = dict(up, params=systemsim.corrupt_params(
                        up["params"], f[1], injector.profile.huge_scale))
                ok, reason = validate_update(
                    up["params"], server["global"],
                    max_norm_mult=policy.max_norm_mult)
                if ok:
                    uploads.append(up)
                    weights.append(result.weights[i])
                    losses.append(result.local_losses[i])
                    state_commits[k] = result.client_states[i]
                else:
                    ftel["rejected_nonfinite"
                         if reason.startswith("nonfinite")
                         else "rejected_norm"] += 1
                    failed.append(k)
        if len(uploads) >= quorum or not failed \
                or attempt >= policy.max_retries:
            break
        # re-dispatch the failed subset after a capped exponential backoff
        # on the (virtual) clock; each retry re-trains from the same
        # round-frozen payload against the current global
        attempt += 1
        ftel["retries"] += 1
        ftel["redispatches"] += len(failed)
        ftel["backoff_wait"] += policy.backoff(attempt)
        pending = failed
    if len(uploads) < quorum:
        ftel["quorum_shortfalls"] += 1
    for k, s in state_commits.items():
        client_states[k] = s
    return uploads, weights, losses


def _exchange_wave(pop, tag, local, injector, dead_hosts, ftel):
    """Allgather one wave/attempt payload across the placement.

    With fault injection on, a peer missing the deadline degrades to the
    ``missing`` set instead of raising (crash-stop: a dead host never
    publishes, so every survivor resolves the same set) and is never
    polled for again (``dead_hosts`` accumulates across waves); without
    an injector the exchange stays strict — a dead peer is a hard error,
    not a fault to tolerate."""
    from repro.population import placement as placement_lib

    pl = pop.placement
    if injector is None:
        return placement_lib.allgather(pl, tag, local), ()
    gathered, missing = placement_lib.allgather_partial(
        pl, tag, local, skip_wait=dead_hosts)
    new = [h for h in missing if h not in dead_hosts]
    if new:
        ftel["host_timeouts"] += len(new)
        dead_hosts.update(new)
    return gathered, missing


def _multihost_fault_round(exec_, ctx, pop, server, payload, client_states,
                           rng, cids, injector, policy, t, dead_hosts):
    """One synchronous fault-tolerant round under multi-host placement.

    Mirrors ``_fault_tolerant_round`` with the fault model made
    placement-aware: every host replicates the full fault/pick draws (the
    streams stay in lockstep), trains only the alive slice it owns, and
    exchanges the slice results per attempt (tag ``roundTTTTTTaAA``).  A
    crashed HOST — injected via ``FaultProfile.host_crash_prob`` (drawn
    per attempt, one uniform per host in host order) or a real peer
    missing the allgather deadline — is a correlated fault over its
    entire owned slice: those clients fail as a block, quorum counts only
    surviving hosts' validated uploads, and the retry loop re-dispatches
    the absent slice with the usual capped backoff.  Uploads travel CLEAN
    through the exchange with the fault draw replayed on every host:
    ``corrupt_params`` is pure and ``validate_update`` deterministic, so
    survivors accept and reject the very same updates.  With
    ``host_crash_prob == 0`` and no deadline misses this is bit-identical
    to the single-host ``_fault_tolerant_round`` on the same seed.
    """
    from repro.core import systemsim
    from repro.core.server import validate_update
    from repro.population import placement as placement_lib

    pl = pop.placement
    ftel = ctx.telemetry["faults"]
    quorum = max(1, int(np.ceil(policy.quorum_frac * len(cids))))
    uploads: list = []
    weights: list = []
    losses: list = []
    state_commits: dict = {}
    host_stats = None
    pending = list(cids)
    attempt = 0
    while pending:
        crashed = ()
        if injector.profile.host_crash_prob > 0.0:
            crashed = injector.draw_host_crashes(pl.n_hosts)
        drawn = [(k, injector.draw()) for k in pending]
        failed = [k for k, f in drawn
                  if f is not None and f[0] in ("crash", "timeout")]
        alive = [(k, f) for k, f in drawn
                 if f is None or f[0] == "corrupt"]
        alive_ids = [k for k, _ in alive]
        # every host consumes the main stream exactly like the single-host
        # run_round would: full alive-order batch picks from sizes alone
        picks = [executor_lib.materialize_picks(
            rng, _SizeOnly(pop.client_n(k)), ctx.batch_size, ctx.epochs,
            ctx.max_batches) for k in alive_ids]
        own = [(j, k) for j, k in enumerate(alive_ids) if pop.owned(k)]
        local: dict = {"idx": [], "uploads": [], "weights": [],
                       "losses": [], "crashed": pl.host_id in crashed}
        new_states: dict = {}
        if own and not local["crashed"]:
            ids = [k for _, k in own]
            pop.pin(ids)
            result = exec_.run_round(
                ctx, server["global"], payload,
                [client_states[k] for k in ids],
                [pop.clients[k] for k in ids], rng, client_ids=ids,
                picks=[picks[j] for j, _ in own])
            pop.unpin(ids)
            new_states = dict(zip(ids, result.client_states))
            local.update(idx=[j for j, _ in own], uploads=result.uploads,
                         weights=[float(w) for w in result.weights],
                         losses=[float(v) for v in result.local_losses])
        local["stats"] = dict(pop.stats(),
                              host_rss_mb=placement_lib.peak_rss_mb(),
                              slab=ctx.placement.stats(),
                              exchange=dict(pl.stats))
        gathered, _ = _exchange_wave(
            pop, f"round{t:06d}a{attempt:02d}", local, injector,
            dead_hosts, ftel)
        host_stats = [g["stats"] if g is not None else None
                      for g in gathered]
        got = {}
        for g in gathered:
            if g is None or g["crashed"]:
                continue
            for jj, j in enumerate(g["idx"]):
                got[int(j)] = (g["uploads"][jj], float(g["weights"][jj]),
                               float(g["losses"][jj]))
        for j, (k, f) in enumerate(alive):
            hit = got.get(j)
            if hit is None:
                owner = pop.sampler.shard_of(int(k)) % pl.n_hosts
                g = gathered[owner]
                if owner in crashed or g is None or g["crashed"]:
                    failed.append(k)        # correlated host fault
                    continue
                raise RuntimeError(
                    f"multi-host fault round {t}: live host {owner} "
                    f"published no upload for client {k} — the placement "
                    f"does not partition the cohort")
            up, w, lv = hit
            if f is not None:
                up = dict(up, params=systemsim.corrupt_params(
                    up["params"], f[1], injector.profile.huge_scale))
            ok, reason = validate_update(
                up["params"], server["global"],
                max_norm_mult=policy.max_norm_mult)
            if ok:
                uploads.append(up)
                weights.append(w)
                losses.append(lv)
                if k in new_states:
                    state_commits[k] = new_states[k]
            else:
                ftel["rejected_nonfinite" if reason.startswith("nonfinite")
                     else "rejected_norm"] += 1
                failed.append(k)
        if len(uploads) >= quorum or not failed \
                or attempt >= policy.max_retries:
            break
        attempt += 1
        ftel["retries"] += 1
        ftel["redispatches"] += len(failed)
        ftel["backoff_wait"] += policy.backoff(attempt)
        pending = failed
    if len(uploads) < quorum:
        ftel["quorum_shortfalls"] += 1
    for k, s in state_commits.items():
        client_states[k] = s
    ctx.telemetry["population"] = dict(pop.stats(), hosts=host_stats)
    return uploads, weights, losses


def _multihost_wave(ctx, inner, pop, global_params, payload, client_states,
                    cids, rng, tag, slots, injector, dead_hosts, ftel):
    """One async dispatch wave under multi-host placement.

    Every host replicates the whole simulation — sampling, the event
    heap, aggregation, server updates — and this function keeps only the
    TRAINING partitioned: each host pre-draws the FULL wave's batch picks
    (main-stream lockstep), draws the wave's host-crash faults (one
    uniform per host in host order, only when ``host_crash_prob > 0``),
    trains the owned slice it is alive for (in fixed-slot chunks so the
    one compiled body serves every wave), publishes the slice under the
    per-wave exchange ``tag``, and reassembles the full wave.  The
    returned per-client ``(upload, weight, loss, fault)`` list is
    byte-identical on every host (each host re-reads its own payload from
    its exchange file), so the ``SystemSim`` heaps — and therefore the
    virtual clock, pops, redispatches and aggregations — stay in lockstep
    with no further coordination.  A host that crashed (injected) or
    missed the exchange deadline (real death, crash-stop) contributes a
    correlated ``("host_crash", "")`` fault over its whole slice: those
    dispatches still occupy the heap with a ``None`` upload and fall to
    the dead path at buffer fill, where the usual retry/backoff machinery
    re-dispatches them.
    """
    from repro.population import placement as placement_lib

    pl = pop.placement
    crashed = ()
    if injector is not None and injector.profile.host_crash_prob > 0.0:
        crashed = injector.draw_host_crashes(pl.n_hosts)
    picks = [executor_lib.materialize_picks(
        rng, _SizeOnly(pop.client_n(c)), ctx.batch_size, ctx.epochs,
        ctx.max_batches) for c in cids]
    own = [(i, c) for i, c in enumerate(cids) if pop.owned(c)]
    local: dict = {"idx": [], "uploads": [], "weights": [], "losses": [],
                   "crashed": pl.host_id in crashed}
    new_states: dict = {}
    if own and not local["crashed"]:
        pop.pin([c for _, c in own])
        groups = ([own[i:i + slots] for i in range(0, len(own), slots)]
                  if slots is not None else [own])
        for group in groups:
            ids = [c for _, c in group]
            result = inner.run_round(
                ctx, global_params, payload,
                [client_states[k] for k in ids],
                [pop.clients[k] for k in ids], rng, client_ids=ids,
                picks=[picks[i] for i, _ in group])
            new_states.update(zip(ids, result.client_states))
            local["idx"].extend(i for i, _ in group)
            local["uploads"].extend(result.uploads)
            local["weights"].extend(float(w) for w in result.weights)
            local["losses"].extend(float(v) for v in result.local_losses)
    local["stats"] = dict(pop.stats(),
                          host_rss_mb=placement_lib.peak_rss_mb(),
                          slab=ctx.placement.stats(),
                          exchange=dict(pl.stats))
    gathered, _ = _exchange_wave(pop, tag, local, injector, dead_hosts,
                                 ftel)
    # per-client fault draws AFTER training, in wave order — the same
    # fault-stream consumption as the single-host launch path
    per_fault = [injector.draw() if injector is not None else None
                 for _ in cids]
    got = {}
    for g in gathered:
        if g is None or g["crashed"]:
            continue
        for jj, i in enumerate(g["idx"]):
            got[int(i)] = (g["uploads"][jj], float(g["weights"][jj]),
                           float(g["losses"][jj]))
    out = []
    for i, c in enumerate(cids):
        hit = got.get(i)
        if hit is None:
            owner = pop.sampler.shard_of(int(c)) % pl.n_hosts
            g = gathered[owner]
            if owner in crashed or g is None or g["crashed"]:
                out.append((None, 0.0, 0.0, ("host_crash", "")))
                continue
            raise RuntimeError(
                f"multi-host wave {tag}: live host {owner} published no "
                f"upload for client {c} — the placement does not "
                f"partition the wave")
        up, w, lv = hit
        fault = per_fault[i]
        if fault is None and c in new_states:
            # healthy dispatch: commit the owned client's local state
            client_states[c] = new_states[c]
        out.append((up, w, lv, fault))
    host_stats = [g["stats"] if g is not None else None for g in gathered]
    return out, host_stats


def _max_client_n(data) -> int:
    """Largest client example count in the population — the shape bound
    fixed-slot waves pin the compiled round body to.  Population facades
    answer through ``max_client_n()`` without materializing cold shards;
    eager ``FederatedData`` scans its client list."""
    fn = getattr(data, "max_client_n", None)
    if fn is not None:
        return int(fn())
    return int(max(d.n for d in data.clients))


def _snapshot_client_states(client_states, n_clients):
    """Checkpoint payload for per-client algorithm state.  The eager dict
    stores every state by value (O(n_clients) — fine at eager scale); a
    population-tier ``ClientStateStore`` snapshots itself instead (warm
    entries by value, the spill set by reference, nothing at all for
    stateless algorithms) so the checkpoint stays O(touched clients)."""
    if hasattr(client_states, "snapshot"):
        return client_states.snapshot()
    return [client_states[k] for k in range(n_clients)]


def _restore_client_states(client_states, saved):
    if hasattr(client_states, "restore") and isinstance(saved, dict):
        client_states.restore(saved)
        return
    if isinstance(saved, dict):
        raise ValueError(
            "checkpoint holds a population state-store snapshot but this "
            "run uses eager data= — resume with the population= it was "
            "written under")
    for k, s in enumerate(saved):
        client_states[k] = s


def _save_checkpoint(ckpt_dir, rnd, algo, server, jrng, rng, injector,
                     records, client_states, n_clients, ftel=None,
                     extra=None, host=None):
    from repro.checkpoint import recovery
    state = {
        "server": server,
        "jrng": jrng,
        "np_rng": recovery.rng_state(rng),
        "fault_rng": (recovery.rng_state(injector.rng)
                      if injector is not None else None),
        # counters travel with the rng so a resumed run's fault telemetry
        # matches the uninterrupted run, not just the post-resume tail
        "fault_counters": (dict(injector.counters)
                           if injector is not None else None),
        "fault_telemetry": dict(ftel) if ftel is not None else None,
        "records": [dataclasses.asdict(r) for r in records],
        "client_states": _snapshot_client_states(client_states, n_clients),
    }
    if extra:
        state.update(extra)
    recovery.save_run_state(ckpt_dir, rnd, state, meta={"algo": algo.name},
                            host=host)


def _run_async(task: PaperTask, algo: Algorithm, data: FederatedData,
               model: ModelBundle, server: dict,
               ctx: "executor_lib.RoundContext",
               exec_: "executor_lib.AsyncExecutor",
               inner: "executor_lib.ClientExecutor",
               rng: np.random.Generator, jrng, *, seed: int, rounds: int,
               eval_every: int, verbose: bool, round_callback, dp,
               n_sample: int, client_states: dict, val_batch,
               pop=None, injector=None, policy=None,
               checkpoint_dir=None, checkpoint_every: int = 1,
               resume: bool = False) -> History:
    """Buffered-asynchronous rounds on a simulated heterogeneous system.

    Event structure (one History record per AGGREGATION, i.e. per global
    version bump):

      * ``n_sample`` clients are always in flight; each dispatch WAVE
        samples idle clients, trains them through the inner executor
        against the CURRENT global (tagging the uploads with its version),
        and schedules their completions on the virtual clock at
        ``now + local_steps / speed`` (``repro.core.systemsim``);
      * an aggregation consumes the ``B`` earliest completions, weights
        them by data size × staleness scale
        (``repro.core.server.async_aggregation_weights``), applies
        ``server_update``, bumps the version, and redials ``B`` fresh
        clients — the server never waits for the straggler tail;
      * within a buffer, updates aggregate in DISPATCH order (arrival
        order only decides membership): deterministic, and in the
        degenerate homogeneous/full-buffer regime bit-compatible with the
        synchronous executors' cohort order;
      * under the ``"fedgkd"`` staleness scheme stale arrivals are also
        absorbed into the KD teacher buffer (``Algorithm.absorb_stale``)
        so their knowledge distills instead of dragging the average.

    All randomness (speeds, availability phases) comes from a child
    stream of the training seed (``systemsim.derive_rng``); the main
    ``rng``/``jrng`` are consumed exactly like the synchronous loop
    (sample, then materialize), which is what makes the equivalence and
    determinism suites exact.

    With fault injection (``injector``/``policy`` from
    ``run_federated(faults=)``) each dispatch additionally draws a fault
    from the dedicated fault stream: crashed/timed-out/invalid
    completions are skipped by the buffer fill (which keeps draining the
    heap until it holds ``B`` VALIDATED updates), the failed client is
    re-dispatched against the current global after a capped exponential
    backoff on the simulated clock (dropped from the fleet after
    ``max_retries`` consecutive failures), and the post-aggregation
    refill tops the fleet back up to ``n_sample`` in flight.

    Fixed-slot waves + pipelining (``AsyncExecutor(wave_slots=,
    pipelined=)``): with fixed slots every dispatch wave pads to the
    buffer size B through the phantom-client masking machinery — refills
    ARE B clients, redispatches pad 1 → B, and the initial ``n_sample``
    wave trains as ceil(n_sample/B) chunks of the same B-slot body — so
    exactly ONE compiled round body serves the whole run
    (``telemetry["compile_count"]``).  Pipelined mode defers every host
    sync to the aggregation: the inner executor returns on-device losses,
    the refill wave's sampling/materialization/teacher-precompute dispatch
    BEFORE the round's eval forces, and ``jax.block_until_ready`` runs
    only on the buffer being aggregated — wave N+1's host+device prologue
    overlaps wave N's training.  Both knobs change scheduling only: the
    aggregated numbers are identical to the single-stream variable-wave
    path (the equivalence tests pin fixed-vs-variable bit-for-bit at zero
    faults and pipelined-vs-single-stream < 1e-5).

    ``checkpoint_dir=``/``resume=`` compose with all of the above: each
    aggregation checkpoints the full run state INCLUDING the simulator
    (clock, event heap with its tagged in-flight upload pytrees, dispatch
    sequence) and the per-client retry counters, so a run killed mid-wave
    resumes into the exact wave it died in and replays the uninterrupted
    history bit-for-bit — faults included (corruption is applied at
    buffer-fill time, never inside the heap, so the snapshot only ever
    holds finite leaves).
    """
    from repro.core import systemsim
    from repro.core.server import async_aggregation_weights

    b = exec_.buffer_size if exec_.buffer_size is not None else n_sample
    if not (1 <= b <= n_sample):
        raise ValueError(
            f"async buffer_size must be in [1, cohort={n_sample}]: a larger "
            f"buffer than the in-flight fleet can never fill (got {b})")
    sim = systemsim.SystemSim(
        data.n_clients, profile=exec_.profile,
        availability=exec_.availability, rng=systemsim.derive_rng(seed),
        base_step_time=exec_.base_step_time)

    def client_work(n: int) -> int:
        steps = pipeline_num_batches(n, ctx.batch_size, ctx.epochs)
        if ctx.max_batches is not None:
            steps = min(steps, ctx.max_batches)
        return steps

    # local work priced lazily from client SIZES (``client_n`` never
    # materializes arrays), memoized per sampled client — the eager
    # per-client list this replaces was O(population) host work
    work_memo: dict[int, int] = {}

    def work_of(k: int) -> int:
        w = work_memo.get(k)
        if w is None:
            w = work_memo[k] = client_work(data.client_n(k))
        return w

    # fixed-slot wave geometry: pin the batched round body's shapes to
    # population-wide maxima (client size -> steps/batch/rows are all
    # monotone in n, so the largest client bounds every wave) and the
    # client axis to the buffer size — one compiled body for the run
    slots = exec_.resolve_wave_slots(b, inner)
    if slots is not None:
        n_max = _max_client_n(data)
        ctx.wave_slots = slots
        ctx.pad_steps = client_work(n_max)
        ctx.pad_batch = min(ctx.batch_size, n_max)
        ctx.pad_rows = n_max
    # pipelined mode: batched inners return on-device losses (forced only
    # at aggregation, below) instead of syncing the host per wave.
    # Multi-host forces the per-wave sync back on: the wave's uploads
    # cross the filesystem exchange as host arrays immediately, so there
    # is nothing left to defer
    multihost = pop is not None and getattr(pop, "multihost", False)
    ctx.deferred = bool(exec_.pipelined and inner.name != "sequential"
                        and not multihost)

    # in-flight ids are the SMALL set (≤ n_sample); sampling excludes them
    # instead of enumerating the O(population) idle complement — for flat
    # data ``sample_cohort(exclude=...)`` reproduces the historical
    # sorted-idle-array draw bit for bit
    in_flight: set[int] = set()
    version = 0
    stale_absorbed = 0
    max_stale = 0.0
    records: list[RoundRecord] = []
    uploads: list[dict] = []
    ftel = ctx.telemetry.get("faults")
    fail_count: dict[int, int] = {}     # consecutive failures per client
    dead_hosts: set = set()     # peers that missed an exchange deadline
    wave_seq = 0    # per-wave exchange tag counter, in lockstep across
    # hosts because every host replays the identical dispatch sequence
    mh_stats: dict = {"hosts": None}    # latest per-host tier telemetry
    ckpt_host = pop.placement.host_id if multihost else None

    def owned_only(ids):
        """Pin/unpin only this host's slice under placement (pure set ops
        either way, but unowned ids must not clutter the pinned set)."""
        return [k for k in ids if pop.owned(k)] if multihost else ids

    def launch(cids: "list[int]", krng, delay: float = 0.0) -> None:
        """Train ``cids`` against the current global and schedule their
        completions (with per-dispatch fault draws when injection is on:
        a faulted dispatch still occupies the heap — inflated by the
        timeout factor for the timeout tail — but its tag marks it dead;
        corruption is applied at buffer-FILL time from the tag, so the
        heap itself only ever holds finite uploads and stays
        checkpointable through ``io.save_pytree``'s non-finite gate).

        In fixed-slot mode the wave trains in chunks of ``slots`` clients
        so every inner call runs the one compiled B-slot body; sampling
        (one ``sample_cohort`` per wave) and the sim dispatch sequence
        are untouched — chunking is invisible to both.

        Under multi-host placement the wave detours through
        ``_multihost_wave``: each host trains only its owned slice and the
        full wave reassembles from the per-wave exchange, but the sim
        dispatch sequence below is identical on every host — the heaps
        (and so the clock, the pops and the aggregations) never diverge."""
        nonlocal wave_seq
        payload = algo.round_payload(server, krng)
        if multihost:
            tag = f"wave{wave_seq:09d}"
            wave_seq += 1
            results, mh_stats["hosts"] = _multihost_wave(
                ctx, inner, pop, server["global"], payload, client_states,
                cids, rng, tag, slots, injector, dead_hosts, ftel)
            for k, (up, w, lv, fault) in zip(cids, results):
                slowdown = (injector.profile.timeout_factor
                            if fault is not None and fault[0] == "timeout"
                            else 1.0)
                in_flight.add(k)
                sim.dispatch(k, work_of(k), tag={
                    "upload": up, "weight": w, "loss": lv,
                    "version": version, "fault": fault},
                    delay=delay, slowdown=slowdown)
            return
        if pop is not None:
            # in-flight clients keep their warm shard / device slab /
            # state-tier entries until their completions aggregate
            pop.pin(cids)
        groups = ([cids[i:i + slots] for i in range(0, len(cids), slots)]
                  if slots is not None else [cids])
        for group in groups:
            result = inner.run_round(
                ctx, server["global"], payload,
                [client_states[k] for k in group],
                [data.clients[k] for k in group], rng, client_ids=group)
            for i, k in enumerate(group):
                fault = injector.draw() if injector is not None else None
                if fault is None:
                    # a failed client's local work is lost: only healthy
                    # dispatches commit their state update
                    client_states[k] = result.client_states[i]
                slowdown = (injector.profile.timeout_factor
                            if fault is not None and fault[0] == "timeout"
                            else 1.0)
                in_flight.add(k)
                sim.dispatch(k, work_of(k), tag={
                    "upload": result.uploads[i],
                    "weight": result.weights[i],
                    "loss": result.local_losses[i], "version": version,
                    "fault": fault}, delay=delay, slowdown=slowdown)

    def dispatch_wave(k_count: int) -> None:
        nonlocal jrng
        if k_count == 0:
            return
        jrng, krng = jax.random.split(jrng)
        # with an EMPTY in-flight set this is the synchronous loop's exact
        # rng.choice call — a seed draws the same cohorts here as in the
        # sync loop; with clients in flight the excluded draw reproduces
        # the historical sorted-idle-array indexing bit for bit
        sampled = data.sample_cohort(rng, k_count, exclude=in_flight)
        launch([int(k) for k in sampled], krng)

    def redispatch(k: int, delay: float) -> None:
        nonlocal jrng
        jrng, krng = jax.random.split(jrng)
        launch([k], krng, delay=delay)

    def fill_buffer() -> list:
        """Drain the heap until it yields ``b`` VALIDATED completions —
        dead (crash/timeout) and rejected updates are skipped, their
        clients re-dispatched with capped exponential backoff (dropped
        from the fleet after ``max_retries`` consecutive failures).  May
        return fewer than ``b`` (even zero) when the whole fleet fails
        out."""
        from repro.core.server import validate_update

        out: list = []
        while len(out) < b and sim.in_flight > 0:
            c = sim.pop()
            fault = c.tag.get("fault")
            if fault is not None and fault[0] == "corrupt":
                # corruption is applied HERE, not at dispatch: the heap
                # only ever holds the clean finite upload plus the fault
                # tag (so checkpoints of in-flight state pass io.py's
                # non-finite gate), and ``corrupt_params`` is pure, so a
                # restored heap replays the same corrupted bytes
                up = c.tag["upload"]
                c.tag["upload"] = dict(up, params=systemsim.corrupt_params(
                    up["params"], fault[1], injector.profile.huge_scale))
            if fault is None or fault[0] == "corrupt":
                ok, reason = validate_update(
                    c.tag["upload"]["params"], server["global"],
                    max_norm_mult=policy.max_norm_mult)
                if ok:
                    out.append(c)
                    fail_count.pop(c.client, None)
                    continue
                ftel["rejected_nonfinite"
                     if reason.startswith("nonfinite")
                     else "rejected_norm"] += 1
            # dead completion: free the slot, retry or drop the client
            in_flight.discard(c.client)
            if pop is not None:
                pop.unpin(owned_only([c.client]))
            fails = fail_count.get(c.client, 0) + 1
            fail_count[c.client] = fails
            if fails <= policy.max_retries:
                delay = policy.backoff(fails)
                ftel["redispatches"] += 1
                ftel["retries"] += 1
                ftel["backoff_wait"] += delay
                redispatch(c.client, delay)
            else:
                ftel["dropped_clients"] += 1
                fail_count.pop(c.client, None)
        return out

    def refill() -> None:
        if injector is None:
            dispatch_wave(b)
        else:
            # permanently dropped clients shrink the fleet below
            # n_sample: top back up (bounded by the idle population)
            want = min(n_sample - len(in_flight),
                       data.n_clients - len(in_flight))
            dispatch_wave(max(0, want))

    def save_ckpt(rnd: int) -> None:
        """Checkpoint the FULL async run state.  Must run AFTER the
        round's refill: the heap snapshot has to contain the wave the
        uninterrupted run would carry into round ``rnd + 1``, or resume
        would aggregate from an under-filled fleet."""
        if checkpoint_dir is None or (
                rnd % checkpoint_every != 0 and rnd != rounds):
            return
        _save_checkpoint(
            checkpoint_dir, rnd, algo, server, jrng, rng, injector,
            records, client_states, data.n_clients, ftel=ftel,
            extra={"sim": sim.state(),
                   "in_flight": sorted(in_flight),
                   "version": version,
                   "stale_absorbed": stale_absorbed,
                   "max_stale": max_stale,
                   "wave_seq": wave_seq,
                   "fail_count": sorted(fail_count.items())},
            host=ckpt_host)

    start_round = 0
    if resume:
        from repro.checkpoint import recovery
        hit = recovery.load_latest_state(checkpoint_dir, host=ckpt_host)
        if multihost:
            # coordinated resume: agree on the newest aggregation EVERY
            # host can load (min over hosts — a host that checkpointed
            # further ahead still has the earlier file), restore it,
            # retire this host's stale wave exchange files, then confirm
            # all hosts restored the same {round, version} before the
            # first wave runs
            from repro.population import placement as placement_lib
            common = placement_lib.resume_barrier(
                pop.placement, hit[2] if hit is not None else None)
            if common is None:
                hit = None
            elif hit is None or hit[2] != common:
                hit = (*recovery.load_state_at(checkpoint_dir, common,
                                               host=ckpt_host), common)
        if hit is not None:
            state, meta, start_round = hit
            if meta.get("algo") not in (None, algo.name):
                raise ValueError(
                    f"resume: checkpoint was written by algo "
                    f"{meta.get('algo')!r}, this run is {algo.name!r}")
            server = state["server"]
            jrng = state["jrng"]
            recovery.restore_rng(rng, state["np_rng"])
            if injector is not None and state.get("fault_rng") is not None:
                recovery.restore_rng(injector.rng, state["fault_rng"])
                if state.get("fault_counters") is not None:
                    injector.counters.update(state["fault_counters"])
                if state.get("fault_telemetry") is not None:
                    ftel.update(state["fault_telemetry"])
            records = [RoundRecord(**d) for d in state["records"]]
            _restore_client_states(client_states, state["client_states"])
            sim.restore(state["sim"])
            in_flight = set(int(k) for k in state["in_flight"])
            version = int(state["version"])
            stale_absorbed = int(state["stale_absorbed"])
            max_stale = float(state["max_stale"])
            fail_count.clear()
            fail_count.update({int(k): int(v)
                               for k, v in state["fail_count"]})
            wave_seq = int(state.get("wave_seq", 0))
            if pop is not None and in_flight:
                # restored in-flight clients must hold their warm/slab
                # pins exactly as they did when the checkpoint was cut
                pop.pin(owned_only(sorted(in_flight)))
        if multihost:
            placement_lib.clear_host_payloads(pop.placement)
            placement_lib.confirm_resume(
                pop.placement, None if hit is None else start_round,
                {"round": None if hit is None else start_round,
                 "version": version, "algo": algo.name})

    # with checkpointing on, the FINAL round refills too: its checkpoint
    # then matches the one an uninterrupted longer run writes at the same
    # round, so a finished run can be EXTENDED (resume with more rounds)
    # bit-identically, not just recovered from a kill
    def wants_refill(t: int) -> bool:
        return t < rounds - 1 or checkpoint_dir is not None

    if start_round == 0:
        dispatch_wave(n_sample)
    for t in range(start_round, rounds):
        t0 = time.perf_counter()
        if injector is None:
            completions = sim.pop_batch(b)
        else:
            completions = fill_buffer()
            if not completions:
                # the whole fleet failed out this aggregation window:
                # hold the global, record the skipped event, redial
                ftel["skipped_rounds"] += 1
                acc, loss = ((records[-1].test_acc, records[-1].test_loss)
                             if records else
                             evaluate(model, server["global"],
                                      data.test_x, data.test_y))
                records.append(RoundRecord(
                    t + 1, acc, loss, 0.0, time.perf_counter() - t0,
                    sim_time=sim.now, version=version))
                if wants_refill(t):
                    dispatch_wave(min(b, data.n_clients - len(in_flight)))
                save_ckpt(t + 1)
                continue
        # canonical aggregation order: dispatch sequence (see docstring)
        completions.sort(key=lambda c: c.seq)
        staleness = [version - c.tag["version"] for c in completions]
        max_stale = max(max_stale, float(max(staleness)))
        agg_uploads = [c.tag["upload"] for c in completions]
        data_weights = [c.tag["weight"] for c in completions]
        weights = async_aggregation_weights(
            data_weights, staleness, exec_.staleness, a=exec_.staleness_a,
            cutoff=exec_.staleness_cutoff, normalize=False)
        # deferred (pipelined) completions carry on-device losses; this
        # float() + the block_until_ready below are the round's ONLY
        # host syncs — everything still in flight stays in flight
        local_losses = [float(c.tag["loss"]) for c in completions]
        if ctx.deferred:
            jax.block_until_ready(agg_uploads)
        if verbose and t == 0:
            tele = ctx.telemetry
            print(f"[{algo.name}] executor route: async/"
                  f"{tele.get('route', inner.name)} (buffer B={b}, "
                  f"staleness={exec_.staleness}, "
                  f"profile={sim.profile.kind})")

        uploads = agg_uploads
        if dp is not None:
            from repro.core import privacy
            uploads = privacy.privatize_uploads(uploads, server["global"],
                                                dp, t)
        server = algo.server_update(server, uploads, weights, model,
                                    val_batch, n_clients=data.n_clients)
        if dp is not None:
            from repro.core import privacy
            server["global"] = privacy.noise_aggregate(server["global"], dp,
                                                       len(uploads), t)
        if exec_.staleness == "fedgkd":
            n_stale = sum(1 for s in staleness if s > 0)
            if n_stale:
                stale_absorbed += n_stale
                server = algo.absorb_stale(server, uploads, staleness,
                                           data_weights, model=model,
                                           val_batch=val_batch)
        version += 1
        for c in completions:
            in_flight.discard(c.client)
        if pop is not None:
            pop.unpin(owned_only([c.client for c in completions]))
            ctx.telemetry["population"] = (
                dict(pop.stats(), hosts=mh_stats["hosts"]) if multihost
                else pop.stats())

        refilled = False
        if ctx.deferred and wants_refill(t):
            # pipelining: dispatch wave N+1 (sampling, slab gather,
            # teacher precompute, device launch) BEFORE this round's
            # eval forces the host — the refill's host prologue and its
            # device work overlap the eval and the wait for the next
            # buffer.  Eval consumes no rng, so hoisting the dispatch
            # past it leaves the sampled history untouched.
            refill()
            refilled = True
        if (t + 1) % eval_every == 0 or t == rounds - 1:
            acc, loss = evaluate(model, server["global"], data.test_x,
                                 data.test_y)
        else:
            acc, loss = ((records[-1].test_acc, records[-1].test_loss)
                         if records else (0.0, 0.0))
        records.append(RoundRecord(
            t + 1, acc, loss, float(np.mean(local_losses)),
            time.perf_counter() - t0, sim_time=sim.now, version=version,
            mean_staleness=float(np.mean(staleness)),
            sampled=tuple(c.client for c in completions)))
        if wants_refill(t) and not refilled:
            refill()
        save_ckpt(t + 1)
        if round_callback is not None:
            round_callback(t + 1, server, model)
        if verbose:
            print(f"[{algo.name}] agg {t+1:3d}/{rounds} v{version} "
                  f"acc={acc:.4f} loss={loss:.4f} "
                  f"local={np.mean(local_losses):.4f} "
                  f"sim_t={sim.now:.1f} stale={np.mean(staleness):.2f}")

    if pop is not None and in_flight:
        # clients still in flight when the run ends would stay pinned —
        # a reused Population would then exempt them from eviction forever
        pop.unpin(owned_only(in_flight))
        ctx.telemetry["population"] = (
            dict(pop.stats(), hosts=mh_stats["hosts"]) if multihost
            else pop.stats())
    ctx.telemetry.update(
        route="async", inner_route=ctx.telemetry.get("route", inner.name),
        buffer_size=b, staleness_scheme=exec_.staleness,
        aggregations=rounds, final_version=version,
        stale_absorbed=stale_absorbed,
        mean_staleness=float(np.mean([r.mean_staleness for r in records])),
        max_staleness=max_stale, sim=sim.stats())
    if injector is not None:
        ftel.update(injector.counters)

    local_acc = 0.0
    if uploads:
        local_acc, _ = evaluate(model, uploads[-1]["params"],
                                data.test_x, data.test_y)
    return History(algo.name, records, server["global"], local_acc,
                   dict(ctx.telemetry))


def make_federated_data(task: PaperTask, alpha: float, seed: int = 0,
                        n_test: int = 1000) -> FederatedData:
    from repro.data.synthetic import make_task_data
    xtr, ytr, xte, yte = make_task_data(task, task.train_size, n_test, seed=seed)
    return FederatedData.from_arrays(xtr, ytr, xte, yte,
                                     n_clients=task.n_clients, alpha=alpha,
                                     seed=seed)
