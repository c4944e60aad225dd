"""Multi-host population placement (``repro.population.placement``).

The contract under test:

  * ownership ``host(cid) = shard_of(cid) % n_hosts`` PARTITIONS the
    population — every client has exactly one owner, so the exchanged
    upload lists reassemble without gaps or double-counts;
  * per-host warm caps are ``warm_cap // n_hosts`` and the slab store
    refuses to materialize unowned clients (placement bugs are loud);
  * the filesystem allgather is atomic and self-describing — every host
    decodes byte-identical payloads, including its own;
  * ``n_hosts == 1`` is INERT: bit-for-bit the single-host history, on
    every executor and algorithm, faults and checkpoints included;
  * the real thing: two worker PROCESSES sharing an exchange dir train
    the same global model bit-identically to each other — sync and
    buffered-async, with and without fault injection — and match the
    in-process single-host run, with each host's ``peak_warm`` inside
    its half of the warm cap;
  * a host that dies mid-run degrades to a correlated host fault for the
    survivors, and the coordinated resume restores every host to the
    same round and replays the uninterrupted history bit-for-bit.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.io import load_flat
from repro.configs.paper import TOY
from repro.core import algorithms, fl_loop
from repro.core.systemsim import FaultProfile
from repro.data.pipeline import ClientData, ClientSlabStore
from repro.population import HostPlacement, Population, allgather
from repro.population.placement import (allgather_partial,
                                        clear_host_payloads, confirm_resume,
                                        publish, resume_barrier)
from repro.sharding import make_array_from_process_local_data_compat

from test_population import _max_param_diff, multidevice

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# --------------------------------------------------------------------------
# HostPlacement: validation / ownership / cap splitting
# --------------------------------------------------------------------------

def test_placement_validation():
    with pytest.raises(ValueError, match="n_hosts"):
        HostPlacement(0, 0)
    with pytest.raises(ValueError, match="out of range"):
        HostPlacement(2, 2, exchange_dir="/tmp/x")
    with pytest.raises(ValueError, match="exchange_dir"):
        HostPlacement(0, 2)                  # multi-host needs the dir
    HostPlacement(0, 1)                      # single host: dir optional


@pytest.mark.parametrize("n_hosts", [1, 2, 3, 5])
def test_ownership_partitions_every_shard(n_hosts):
    placements = [HostPlacement(h, n_hosts, exchange_dir="/tmp/x")
                  for h in range(n_hosts)]
    for shard in range(17):
        owners = [p.owns_shard(shard) for p in placements]
        assert sum(owners) == 1              # exactly one owner, never zero


def test_split_cap():
    p = HostPlacement(0, 2, exchange_dir="/tmp/x")
    assert p.split_cap(None) is None
    assert p.split_cap(16) == 8
    assert p.split_cap(1) == 1               # floor: never a zero cap
    assert HostPlacement(0, 1).split_cap(16) == 16


def test_population_placement_splits_warm_cap(tmp_path):
    pl = HostPlacement(1, 2, exchange_dir=str(tmp_path))
    pop = Population.synthetic(40, warm_cap=16, shard_size=8,
                               min_n=3, max_n=6, placement=pl)
    assert pop.store.warm_cap == 8 and pop.multihost
    # ownership partitions the population across the two host views
    other = Population.synthetic(40, warm_cap=16, shard_size=8,
                                 min_n=3, max_n=6,
                                 placement=HostPlacement(
                                     0, 2, exchange_dir=str(tmp_path)))
    for cid in range(40):
        assert pop.owned(cid) != other.owned(cid)
    # probing shapes must not warm an unowned client
    pop.probe_client()
    assert len(pop.store.warm) == 0


def test_slab_store_refuses_unowned_clients():
    store = ClientSlabStore(owns=lambda cid: cid % 2 == 0)
    dev = jax.devices()[0]
    data = ClientData(np.ones((4, 2), np.float32), np.zeros(4, np.int64))
    store.get(2, data, dev)                  # owned: fine
    with pytest.raises(ValueError, match="not owned"):
        store.get(1, data, dev)


# --------------------------------------------------------------------------
# the filesystem allgather + the process-local-data shim
# --------------------------------------------------------------------------

def test_allgather_roundtrip(tmp_path):
    p0 = HostPlacement(0, 2, exchange_dir=str(tmp_path), timeout_s=10)
    p1 = HostPlacement(1, 2, exchange_dir=str(tmp_path), timeout_s=10)
    mine = {"idx": [0, 2], "uploads": [np.arange(6, dtype=np.float32),
                                      np.eye(2)],
            "weights": [1.5, 2.0], "stats": {"peak_warm": 3}}
    theirs = {"idx": [1], "uploads": [np.full((3,), 7.0)],
              "weights": [0.5], "stats": {"peak_warm": 2}}
    publish(p1, "round000000", theirs)       # peer already landed
    got = allgather(p0, "round000000", mine)
    assert len(got) == 2
    # this host's payload round-trips through ITS OWN file too
    np.testing.assert_array_equal(got[0]["uploads"][0], mine["uploads"][0])
    assert got[0]["uploads"][0].dtype == np.float32
    assert got[0]["idx"] == [0, 2] and got[0]["weights"] == [1.5, 2.0]
    np.testing.assert_array_equal(got[1]["uploads"][0], theirs["uploads"][0])
    assert got[1]["stats"]["peak_warm"] == 2


def test_allgather_times_out_naming_missing_hosts_and_tag(tmp_path):
    # the error must name EVERY missing host and the exchange tag — on a
    # real topology that is the difference between restarting one worker
    # and hunting a deadlock
    p0 = HostPlacement(0, 3, exchange_dir=str(tmp_path), timeout_s=0.2)
    with pytest.raises(RuntimeError,
                       match=r"'round000001'.*host\(s\) \[1, 2\]"):
        allgather(p0, "round000001", {"idx": []})
    assert p0.stats["timeouts"] == 1
    assert p0.stats["last_missing"] == [1, 2]
    assert p0.stats["last_missing_tag"] == "round000001"


def test_allgather_partial_degrades_and_skips_dead_hosts(tmp_path):
    p0 = HostPlacement(0, 2, exchange_dir=str(tmp_path), timeout_s=0.2)
    payloads, missing = allgather_partial(p0, "wave000000000", {"x": 1})
    assert missing == (1,)
    assert payloads[1] is None and payloads[0]["x"] == 1
    # a peer already declared dead costs one existence check, not a
    # full timeout, on every subsequent exchange
    p1 = HostPlacement(0, 2, exchange_dir=str(tmp_path), timeout_s=60)
    t0 = time.monotonic()
    payloads, missing = allgather_partial(p1, "wave000000001", {"x": 2},
                                          skip_wait={1})
    assert missing == (1,) and payloads[0]["x"] == 2
    assert time.monotonic() - t0 < 10


def test_resume_barrier_agrees_on_min_round(tmp_path):
    p0 = HostPlacement(0, 2, exchange_dir=str(tmp_path), timeout_s=10)
    p1 = HostPlacement(1, 2, exchange_dir=str(tmp_path), timeout_s=10)
    publish(p1, "resume-avail", {"avail": 7})    # peer got further ahead
    assert resume_barrier(p0, 3) == 3
    # and the slower host's view agrees
    assert resume_barrier(p1, 7) == 3


def test_resume_barrier_all_fresh_and_mixed(tmp_path):
    fresh = tmp_path / "fresh"
    p0 = HostPlacement(0, 2, exchange_dir=str(fresh), timeout_s=10)
    p1 = HostPlacement(1, 2, exchange_dir=str(fresh), timeout_s=10)
    publish(p1, "resume-avail", {"avail": None})
    assert resume_barrier(p0, None) is None      # everyone starts fresh
    mixed = tmp_path / "mixed"
    p0 = HostPlacement(0, 2, exchange_dir=str(mixed), timeout_s=10)
    p1 = HostPlacement(1, 2, exchange_dir=str(mixed), timeout_s=10)
    publish(p1, "resume-avail", {"avail": None})
    with pytest.raises(RuntimeError, match="mixed fresh/resume"):
        resume_barrier(p0, 4)


def test_confirm_resume_validates_and_retires_phase1(tmp_path):
    p0 = HostPlacement(0, 2, exchange_dir=str(tmp_path), timeout_s=10)
    p1 = HostPlacement(1, 2, exchange_dir=str(tmp_path), timeout_s=10)
    publish(p0, "resume-avail", {"avail": 3})
    meta = {"round": 3, "version": 9, "algo": "fedavg"}
    publish(p1, "resume-ok-r000003", dict(meta))
    confirm_resume(p0, 3, meta)                  # peers agree: fine
    # completing the barrier retires this host's phase-1 file
    assert not os.path.exists(str(tmp_path / "resume-avail_host000.npz"))
    # a peer that restored DIFFERENT state fails loudly before any wave
    publish(p1, "resume-ok-r000004", {"round": 4, "version": 9,
                                      "algo": "fedavg"})
    with pytest.raises(RuntimeError, match="diverged"):
        confirm_resume(p0, 4, {"round": 4, "version": 11, "algo": "fedavg"})


def test_clear_host_payloads_removes_own_wave_files_only(tmp_path):
    p0 = HostPlacement(0, 2, exchange_dir=str(tmp_path), timeout_s=10)
    p1 = HostPlacement(1, 2, exchange_dir=str(tmp_path), timeout_s=10)
    publish(p0, "wave000000004", {"x": 1})
    publish(p0, "round000002a01", {"x": 2})
    publish(p0, "resume-avail", {"avail": 2})
    publish(p1, "wave000000004", {"x": 3})
    assert clear_host_payloads(p0) == 2          # own wave/round files only
    left = sorted(os.listdir(tmp_path))
    assert left == ["resume-avail_host000.npz", "wave000000004_host001.npz"]


def test_make_array_from_process_local_data_shim_single_device():
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = make_array_from_process_local_data_compat(sharding, x)
    np.testing.assert_array_equal(np.asarray(out), x)
    assert out.sharding.is_equivalent_to(sharding, x.ndim)


@multidevice
def test_make_array_shim_matches_device_put_on_mesh():
    from repro.launch.mesh import make_clients_mesh

    mesh = make_clients_mesh()
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("clients"))
    n = len(jax.devices())
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    out = make_array_from_process_local_data_compat(sharding, x)
    ref = jax.device_put(x, sharding)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# --------------------------------------------------------------------------
# n_hosts == 1 is inert: bit-for-bit the single-host history
# --------------------------------------------------------------------------

def _tiny_task():
    return dataclasses.replace(TOY, n_clients=12, participation=0.25,
                               rounds=2, local_epochs=1, batch_size=8)


def _tiny_pop(placement=None):
    return Population.synthetic(12, warm_cap=8, shard_size=4, min_n=5,
                                max_n=9, placement=placement)


@pytest.mark.parametrize("name", ["fedavg", "fedgkd"])
@pytest.mark.parametrize("spec", ["sequential", "vmap", "async"])
def test_n_hosts_1_bit_identical(name, spec):
    task = _tiny_task()
    h0 = fl_loop.run_federated(task, algorithms.make(name),
                               population=_tiny_pop(), seed=0,
                               executor=spec, width=4)
    h1 = fl_loop.run_federated(task, algorithms.make(name),
                               population=_tiny_pop(HostPlacement(0, 1)),
                               seed=0, executor=spec, width=4)
    assert _max_param_diff(h0.final_params, h1.final_params) == 0.0
    for r0, r1 in zip(h0.records, h1.records):
        assert r0.sampled == r1.sampled
        assert r0.mean_local_loss == r1.mean_local_loss


@multidevice
def test_n_hosts_1_bit_identical_shard_map():
    task = _tiny_task()
    h0 = fl_loop.run_federated(task, algorithms.make("fedgkd"),
                               population=_tiny_pop(), seed=0,
                               executor="shard_map", width=4)
    h1 = fl_loop.run_federated(task, algorithms.make("fedgkd"),
                               population=_tiny_pop(HostPlacement(0, 1)),
                               seed=0, executor="shard_map", width=4)
    assert _max_param_diff(h0.final_params, h1.final_params) == 0.0


def test_multihost_rejects_unsupported_compositions(tmp_path):
    # async / faults / checkpointing all compose with placement now —
    # only differential privacy is still fenced off
    from repro.core.privacy import DPConfig
    task = _tiny_task()
    algo = algorithms.make("fedavg")
    pop = _tiny_pop(HostPlacement(0, 2, exchange_dir=str(tmp_path),
                                  timeout_s=1))
    with pytest.raises(NotImplementedError, match="dp"):
        fl_loop.run_federated(task, algo, population=pop, seed=0,
                              executor="vmap", width=4, dp=DPConfig())


def test_n_hosts_1_inert_with_faults_and_checkpoint(tmp_path):
    # host_crash_prob only ever draws under a real multi-host placement:
    # an n_hosts=1 run with a nonzero probability must replay the exact
    # single-host fault stream (and write the same ``state_`` checkpoints)
    task = _tiny_task()
    kw = dict(seed=0, executor="async", width=4, checkpoint_every=1,
              faults=FaultProfile(crash_prob=0.2, corrupt_prob=0.2,
                                  host_crash_prob=0.5))
    h0 = fl_loop.run_federated(task, algorithms.make("fedavg"),
                               population=_tiny_pop(),
                               checkpoint_dir=str(tmp_path / "a"), **kw)
    h1 = fl_loop.run_federated(task, algorithms.make("fedavg"),
                               population=_tiny_pop(HostPlacement(0, 1)),
                               checkpoint_dir=str(tmp_path / "b"), **kw)
    assert _max_param_diff(h0.final_params, h1.final_params) == 0.0
    assert h1.telemetry["faults"]["host_crashes"] == 0
    assert sorted(os.listdir(tmp_path / "a")) == \
        sorted(os.listdir(tmp_path / "b"))
    assert any(f.startswith("state_0") for f in os.listdir(tmp_path / "b"))


# --------------------------------------------------------------------------
# the real thing: 2 worker processes over a shared exchange dir
# --------------------------------------------------------------------------

_WORKER = """\
import dataclasses, json, os, sys
import numpy as np
host, n_hosts = int(sys.argv[1]), int(sys.argv[2])
exch, out, algo_name, spec = sys.argv[3], sys.argv[4], sys.argv[5], sys.argv[6]
cfg = json.loads(sys.argv[7]) if len(sys.argv) > 7 else {}
from repro.configs.paper import TOY
from repro.core import algorithms, fl_loop
from repro.population import Population, HostPlacement
from repro.checkpoint.io import save_pytree
import jax
pl = HostPlacement(host, n_hosts, exchange_dir=exch,
                   timeout_s=cfg.get("timeout_s", 180))
pop = Population.synthetic(50, warm_cap=32, shard_size=4, min_n=5, max_n=9,
                           placement=pl)
task = dataclasses.replace(TOY, n_clients=50, participation=0.2,
                           rounds=cfg.get("rounds", 2), local_epochs=1,
                           batch_size=8)
kw = {}
if cfg.get("faults"):
    from repro.core.systemsim import FaultProfile
    kw["faults"] = FaultProfile(**cfg["faults"])
if cfg.get("checkpoint_dir"):
    kw["checkpoint_dir"] = cfg["checkpoint_dir"]
    kw["resume"] = bool(cfg.get("resume"))
die_at = cfg.get("die_at_round")
if die_at is not None and host == cfg.get("die_host", 0):
    # hard host kill right AFTER that round's checkpoint was cut (the
    # callback runs after save_ckpt): no cleanup, no exchange goodbye
    kw["round_callback"] = (
        lambda rnd, server, model: os._exit(17) if rnd == die_at else None)
h = fl_loop.run_federated(task, algorithms.make(algo_name), population=pop,
                          seed=0, executor=spec, width=4, **kw)
stats = h.telemetry["population"]
flat = {f"p{i:03d}": np.asarray(x)
        for i, x in enumerate(jax.tree_util.tree_leaves(h.final_params))}
flat["acc"] = np.float64(h.final_acc)
flat["peak_warm"] = np.int64(stats["peak_warm"])
flat["warm_cap"] = np.int64(stats["warm_cap"])
flat["n_host_stats"] = np.int64(len(stats.get("hosts") or []))
flat["accs"] = np.asarray([r.test_acc for r in h.records], np.float64)
flat["losses"] = np.asarray([r.mean_local_loss for r in h.records],
                            np.float64)
flat["sampled"] = np.asarray(
    [c for r in h.records for c in (*(r.sampled or ()), -1)], np.int64)
ft = h.telemetry.get("faults") or {}
for key in ("host_crashes", "host_timeouts", "crashes", "corrupt_injected",
            "retries", "dropped_clients", "quorum_shortfalls"):
    flat["f_" + key] = np.int64(ft.get(key, -1))
save_pytree(out, flat)
"""

def _spawn_workers(tmp_path, algo, spec, n_hosts=2, xla_flags=None,
                   cfg=None, hosts=None, expect_rc=None, exch=None,
                   timeout=600):
    tmp_path.mkdir(parents=True, exist_ok=True)
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    exch = str(tmp_path / "exchange") if exch is None else exch
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    hosts = list(range(n_hosts)) if hosts is None else list(hosts)
    outs = {h: str(tmp_path / f"host{h}.npz") for h in hosts}
    extra = [json.dumps(cfg)] if cfg else []
    procs = {h: subprocess.Popen(
        [sys.executable, str(worker), str(h), str(n_hosts), exch,
         outs[h], algo, spec, *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for h in hosts}
    for h, p in procs.items():
        out, _ = p.communicate(timeout=timeout)
        want = 0 if expect_rc is None else expect_rc.get(h, 0)
        assert p.returncode == want, (
            f"host {h} worker exited {p.returncode} (wanted {want}):\n{out}")
    return [load_flat(outs[h]) for h in hosts if os.path.exists(outs[h])]


def _assert_hosts_identical(h0, h1):
    """Both hosts' outputs must agree BITWISE — they consumed
    byte-identical exchange inputs and replayed the same simulation.
    ``peak_warm`` is the one per-host value (each host warms only its
    owned slice)."""
    for k in sorted(h0):
        if k != "peak_warm":
            np.testing.assert_array_equal(h0[k], h1[k], err_msg=k)


def _param_diff_vs(ref_history, flat):
    keys = sorted(k for k in flat if k.startswith("p"))
    leaves = jax.tree_util.tree_leaves(ref_history.final_params)
    return max(float(np.max(np.abs(np.asarray(x) - flat[k])))
               for k, x in zip(keys, leaves))


def _reference_history(algo, spec, rounds=2, **kw):
    task = dataclasses.replace(TOY, n_clients=50, participation=0.2,
                               rounds=rounds, local_epochs=1, batch_size=8)
    pop = Population.synthetic(50, warm_cap=32, shard_size=4, min_n=5,
                               max_n=9)
    return fl_loop.run_federated(task, algorithms.make(algo),
                                 population=pop, seed=0, executor=spec,
                                 width=4, **kw)


@pytest.mark.parametrize("algo", ["fedavg", "fedgkd"])
def test_two_process_run_matches_single_host(tmp_path, algo):
    """The tentpole acceptance: two processes, shared exchange dir, each
    owning half the shards — identical global params on both hosts,
    matching the single-host run, with per-host ``peak_warm`` inside its
    half of the global warm cap."""
    h0, h1 = _spawn_workers(tmp_path, algo, "vmap")
    keys = sorted(k for k in h0 if k.startswith("p"))
    # hosts agree bitwise: they aggregated byte-identical exchange inputs
    for k in keys:
        np.testing.assert_array_equal(h0[k], h1[k])
    assert float(h0["acc"]) == float(h1["acc"])
    # telemetry aggregated from BOTH hosts on each host
    assert int(h0["n_host_stats"]) == 2 and int(h1["n_host_stats"]) == 2
    # each host stayed inside its half of the global cap (32 // 2 = 16)
    for flat in (h0, h1):
        assert int(flat["warm_cap"]) == 16
        assert int(flat["peak_warm"]) <= 16
    # and the distributed run matches the single-host history
    ref = _reference_history(algo, "vmap")
    leaves = jax.tree_util.tree_leaves(ref.final_params)
    diff = max(float(np.max(np.abs(np.asarray(x) - h0[k])))
               for k, x in zip(keys, leaves))
    assert diff < 1e-5                       # measured 0.0 on CPU


def test_two_process_async_matches_single_host(tmp_path):
    """Async wave protocol under placement: two processes, per-wave
    exchange tags, each training only its owned slice of the wave's fixed
    slots — both hosts replay the identical simulation (clock, versions,
    aggregation membership) and match the single-host async run."""
    h0, h1 = _spawn_workers(tmp_path, "fedavg", "async")
    _assert_hosts_identical(h0, h1)
    assert int(h0["n_host_stats"]) == 2
    for flat in (h0, h1):
        assert int(flat["peak_warm"]) <= 16
    ref = _reference_history("fedavg", "async")
    assert _param_diff_vs(ref, h0) < 1e-5        # measured 0.0 on CPU
    # the aggregation membership (completions per round) is identical —
    # the event heaps never diverged from the single-host simulation
    ref_sampled = np.asarray(
        [c for r in ref.records for c in (*(r.sampled or ()), -1)],
        np.int64)
    np.testing.assert_array_equal(h0["sampled"], ref_sampled)


def test_two_process_async_faults_bit_identical(tmp_path):
    """Correlated host faults: with ``host_crash_prob`` on, whole owned
    slices fail as a block, yet both hosts draw the same fault stream and
    stay bitwise in lockstep through retries and re-dispatches."""
    cfg = {"rounds": 3, "faults": {"crash_prob": 0.1, "corrupt_prob": 0.1,
                                   "timeout_prob": 0.05,
                                   "host_crash_prob": 0.3}}
    h0, h1 = _spawn_workers(tmp_path, "fedavg", "async", cfg=cfg)
    _assert_hosts_identical(h0, h1)
    assert int(h0["f_host_crashes"]) > 0         # injection actually fired
    assert int(h0["f_host_timeouts"]) == 0       # nobody really died


def test_two_process_sync_faults_match_single_host(tmp_path):
    """With ``host_crash_prob == 0`` the placement-aware fault round
    consumes the fault/pick streams exactly like the single-host
    ``_fault_tolerant_round`` — same survivors, same retries, same
    aggregate."""
    cfg = {"faults": {"crash_prob": 0.2, "corrupt_prob": 0.2}}
    h0, h1 = _spawn_workers(tmp_path, "fedavg", "vmap", cfg=cfg)
    _assert_hosts_identical(h0, h1)
    ref = _reference_history("fedavg", "vmap",
                             faults=FaultProfile(crash_prob=0.2,
                                                 corrupt_prob=0.2))
    assert _param_diff_vs(ref, h0) < 1e-5
    assert int(h0["f_crashes"]) == ref.telemetry["faults"]["crashes"]
    assert int(h0["f_retries"]) == ref.telemetry["faults"]["retries"]


def test_sync_deadline_miss_degrades_to_host_crash(tmp_path):
    """Host 1 is never spawned: with fault tolerance on, the survivor
    treats the missed exchange deadline as a crashed peer (a correlated
    fault over its whole slice, not a hang) and completes on its own
    validated uploads."""
    cfg = {"timeout_s": 3, "faults": {"crash_prob": 0.05}}
    (h0,) = _spawn_workers(tmp_path, "fedavg", "vmap", cfg=cfg, hosts=[0])
    assert int(h0["f_host_timeouts"]) == 1       # declared dead ONCE, then
    assert np.isfinite(float(h0["acc"]))         # skipped, never re-polled


@pytest.mark.slow
def test_kill_one_host_then_coordinated_resume_bit_identical(tmp_path):
    """The recovery acceptance: hard-kill host 0 right after round 2's
    checkpoint (host 1 degrades and runs ahead alone), then restart BOTH
    hosts with ``resume=True`` — the resume barrier agrees on round 2
    (min over hosts), host 1 abandons its degraded solo tail, stale wave
    exchange files are retired, and the replayed history is bit-identical
    to the uninterrupted 2-host run, faults included."""
    cfg = {"rounds": 4, "timeout_s": 20,
           "faults": {"crash_prob": 0.1, "corrupt_prob": 0.1,
                      "host_crash_prob": 0.2}}
    r0, r1 = _spawn_workers(tmp_path / "ref", "fedavg", "async",
                            cfg={**cfg, "checkpoint_dir":
                                 str(tmp_path / "ck_ref")})
    _assert_hosts_identical(r0, r1)

    ck = str(tmp_path / "ck")
    kill = tmp_path / "kill"
    got = _spawn_workers(kill, "fedavg", "async",
                         cfg={**cfg, "checkpoint_dir": ck,
                              "die_at_round": 2, "die_host": 0},
                         expect_rc={0: 17})
    assert len(got) == 1                         # only host 1 finished
    assert int(got[0]["f_host_timeouts"]) == 1   # it saw host 0 die
    # host 1 checkpointed past the kill point; host 0 stopped at round 2
    assert os.path.exists(os.path.join(ck, "state_host001_000004.npz"))
    assert not os.path.exists(os.path.join(ck, "state_host000_000003.npz"))

    # coordinated restart over the SAME exchange dir (stale wave payloads
    # from the degraded solo run must be retired, not trusted)
    o0, o1 = _spawn_workers(tmp_path / "res", "fedavg", "async",
                            cfg={**cfg, "checkpoint_dir": ck,
                                 "resume": True},
                            exch=str(kill / "exchange"))
    _assert_hosts_identical(o0, o1)
    for k in sorted(r0):
        if k != "peak_warm":
            np.testing.assert_array_equal(o0[k], r0[k], err_msg=k)


@pytest.mark.slow
def test_two_process_shard_map_run(tmp_path):
    """2 processes × 8 forced host devices each, shard_map route: the
    cohort slice shards over each host's LOCAL device mesh and the
    result still matches the single-host shard_map run."""
    h0, h1 = _spawn_workers(
        tmp_path, "fedavg", "shard_map",
        xla_flags="--xla_force_host_platform_device_count=8")
    keys = sorted(k for k in h0 if k.startswith("p"))
    for k in keys:
        np.testing.assert_array_equal(h0[k], h1[k])
    assert int(h0["peak_warm"]) <= 16


# --------------------------------------------------------------------------
# leaving the emulator: a real jax.distributed topology
# --------------------------------------------------------------------------

_DIST_WORKER = """\
import dataclasses, sys
import numpy as np
rank, n, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
exch, out, spec = sys.argv[4], sys.argv[5], sys.argv[6]
from repro.launch import distributed
info = distributed.initialize(coord, n, rank)
assert info["process_count"] == n, info
import jax
from repro.configs.paper import TOY
from repro.core import algorithms, fl_loop
from repro.population import Population
from repro.checkpoint.io import save_pytree
pl = distributed.placement_from_runtime(exch, timeout_s=180)
assert (pl.host_id, pl.n_hosts) == (rank, n)
pop = Population.synthetic(50, warm_cap=32, shard_size=4, min_n=5, max_n=9,
                           placement=pl)
task = dataclasses.replace(TOY, n_clients=50, participation=0.2, rounds=2,
                           local_epochs=1, batch_size=8)
h = fl_loop.run_federated(task, algorithms.make("fedavg"), population=pop,
                          seed=0, executor=spec, width=4)
flat = {f"p{i:03d}": np.asarray(x)
        for i, x in enumerate(jax.tree_util.tree_leaves(h.final_params))}
flat["acc"] = np.float64(h.final_acc)
flat["procs"] = np.int64(info["process_count"])
flat["global_devices"] = np.int64(info["global_devices"])
save_pytree(out, flat)
"""


def _spawn_distributed(tmp_path, spec, xla_flags=None, timeout=600):
    from repro.launch.distributed import find_free_port

    worker = tmp_path / "dist_worker.py"
    worker.write_text(_DIST_WORKER)
    coord = f"127.0.0.1:{find_free_port()}"
    exch = str(tmp_path / "exchange")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    outs = [str(tmp_path / f"rank{r}.npz") for r in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, str(worker), str(r), "2", coord, exch,
         outs[r], spec], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in range(2)]
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"rank {r} worker failed:\n{out}"
    return [load_flat(o) for o in outs]


def test_distributed_global_array_stitch():
    """A REAL 2-process ``jax.distributed`` topology on CPU (gloo): the
    smoke CLI stitches a global array from process-local shards through
    ``make_array_from_process_local_data_compat`` across processes,
    unreachable single-process — and every rank sums it identically."""
    from repro.launch.distributed import find_free_port

    coord = f"127.0.0.1:{find_free_port()}"
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    procs = [subprocess.Popen(
        [sys.executable, "-m", "repro.launch.distributed",
         "--coordinator", coord, "--num-processes", "2",
         "--process-id", str(r)], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in range(2)]
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {r} smoke failed:\n{out}"
        assert "global_devices=4" in out


def test_distributed_two_process_fl_run(tmp_path):
    """The multi-host federated loop on a live ``jax.distributed``
    topology, placement derived from ``jax.process_index()`` — identical
    params on both ranks, matching the single-host run."""
    h0, h1 = _spawn_distributed(tmp_path, "vmap")
    assert int(h0["procs"]) == 2 and int(h0["global_devices"]) == 2
    for k in sorted(h0):
        np.testing.assert_array_equal(h0[k], h1[k], err_msg=k)
    ref = _reference_history("fedavg", "vmap")
    assert _param_diff_vs(ref, h0) < 1e-5


@pytest.mark.slow
def test_distributed_shard_map_local_mesh(tmp_path):
    """2 ranks × 2 forced host devices: the shard_map executor detects
    ``jax.process_count() > 1`` and shards each rank's cohort slice over
    its LOCAL device mesh (``make_local_clients_mesh``)."""
    h0, h1 = _spawn_distributed(
        tmp_path, "shard_map",
        xla_flags="--xla_force_host_platform_device_count=2")
    assert int(h0["global_devices"]) == 4
    for k in sorted(h0):
        np.testing.assert_array_equal(h0[k], h1[k], err_msg=k)
