"""Multi-host population placement benchmark: per-host memory bounds.

Measures the tentpole claim of the multi-host placement layer
(``repro.population.placement``): splitting a million-client population
across N host processes divides the warm/hot working set — each host's
``peak_warm`` stays inside ``warm_cap // n_hosts`` and its peak RSS lands
measurably below the single-host figure, while the 2-process shard_map run
still completes its rounds through the filesystem allgather exchange.

The coordinator spawns every measured run as a FRESH subprocess (its own
``--worker`` mode) so each VmHWM high-water mark is clean:

  * one single-host worker (``n_hosts=1``) — the baseline figure;
  * ``--n-hosts`` workers sharing an exchange dir — the distributed run.

Clients are deliberately fat (``--min-n/--max-n`` rows of ``--dim``
features) so the warm+hot tiers dominate interpreter noise in the RSS
comparison; ``max_batches_per_client`` keeps the CPU compute tiny.

Writes ``BENCH_multihost.json`` (one case per host, keyed by the ``host``
field) which the nightly ``multihost-bench`` job gates through
``compare_bench.py`` — ``peak_host_rss_mb`` and ``peak_warm`` are
lower-is-better.  The run itself FAILS in place if a host breaks its warm
bound or the per-host RSS is not below the single-host measurement.

``--chaos`` runs the fault-composition cases instead (``--all`` runs
both): the async executor under correlated host-crash + client faults
(``async_client_updates_per_sec``, higher-is-better — aggregated client
updates per wall-second while the fleet degrades and recovers) and a
mid-run hard kill of host 0 followed by a coordinated resume of the full
topology (``host_crash_recovery_rounds``, lower-is-better — rounds
replayed past the agreed restore point; sensitive to both the checkpoint
cadence and the min-over-hosts resume barrier).  The nightly
``multihost-chaos`` job gates these against the same committed baseline.

    PYTHONPATH=src python benchmarks/multihost_bench.py --host-devices 8
    PYTHONPATH=src python benchmarks/multihost_bench.py \
        --population 100000 --rounds 2            # faster local smoke
    PYTHONPATH=src python benchmarks/multihost_bench.py --chaos
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _worker(args) -> int:
    """One measured training run (single-host baseline or one rank)."""
    import jax

    from repro.configs.paper import TOY
    from repro.core import algorithms, fl_loop
    from repro.population import (HostPlacement, Population, peak_rss_mb)

    n, k = args.population, args.cohort
    placement = None
    if args.n_hosts > 1:
        placement = HostPlacement(args.host, args.n_hosts,
                                  exchange_dir=args.exchange,
                                  timeout_s=args.timeout)
    population = Population.synthetic(
        n, warm_cap=args.warm_cap, shard_size=args.shard_size,
        dim=args.dim, min_n=args.min_n, max_n=args.max_n, seed=0,
        n_test=128, placement=placement)
    task = dataclasses.replace(TOY, n_clients=n, participation=k / n,
                               rounds=args.rounds, local_epochs=1,
                               batch_size=64, feat_dim=args.dim)
    route = args.executor or ("shard_map" if len(jax.devices()) > 1
                              else "vmap")
    kw = {}
    chaos = bool(args.crash_prob or args.corrupt_prob
                 or args.host_crash_prob)
    if chaos:
        from repro.core.systemsim import FaultProfile
        kw["faults"] = FaultProfile(crash_prob=args.crash_prob,
                                    corrupt_prob=args.corrupt_prob,
                                    host_crash_prob=args.host_crash_prob)
    if args.ckpt:
        kw["checkpoint_dir"] = args.ckpt
        kw["resume"] = args.resume
    if args.die_at_round:
        die_at = args.die_at_round
        # hard kill mid-run: no atexit, no flushed result file — the
        # coordinator expects rc 17 and reads the surviving hosts only
        kw["round_callback"] = (
            lambda rnd, server, model: os._exit(17) if rnd == die_at
            else None)
    t0 = time.perf_counter()
    hist = fl_loop.run_federated(task, algorithms.make("fedavg"),
                                 population=population, seed=0,
                                 executor=route, width=args.width,
                                 eval_every=max(args.rounds, 1),
                                 max_batches_per_client=4, **kw)
    wall = time.perf_counter() - t0
    stats = hist.telemetry["population"]
    updates = sum(len(r.sampled or ()) for r in hist.records)
    result = {"host": (f"host{args.host}" if args.n_hosts > 1
                       else "single"),
              "n_hosts": args.n_hosts, "executor": route,
              "devices": len(jax.devices()),
              "wall_s": round(wall, 2),
              "client_updates": updates,
              "peak_host_rss_mb": round(peak_rss_mb(), 1),
              "final_acc": hist.records[-1].test_acc,
              **{f"tier_{key}": val for key, val in stats.items()
                 if isinstance(val, (int, float))},
              "peak_warm": int(stats["peak_warm"]),
              "warm_cap": stats["warm_cap"]}
    if route == "async":
        result["async_client_updates_per_sec"] = round(updates / wall, 2)
    if chaos:
        result["faults"] = (f"crash{args.crash_prob}"
                            f"+corrupt{args.corrupt_prob}"
                            f"+host{args.host_crash_prob}")
        ftel = hist.telemetry.get("faults") or {}
        for key in ("host_crashes", "host_timeouts", "crashes",
                    "corrupt_injected", "retries", "dropped_clients"):
            result[f"f_{key}"] = int(ftel.get(key, 0))
    with open(args.result, "w") as f:
        json.dump(result, f)
    print(f"[{result['host']}] {args.rounds} rounds x K={k} [{route}]: "
          f"{wall:.1f} s wall, peak RSS {result['peak_host_rss_mb']:.0f} MB, "
          f"peak_warm {result['peak_warm']} (cap {result['warm_cap']})")
    return 0


def _spawn(args, host: int, n_hosts: int, exchange: str,
           result: str, extra=()) -> subprocess.Popen:
    cmd = [sys.executable, __file__, "--worker", "--host", str(host),
           "--n-hosts", str(n_hosts), "--result", result,
           "--population", str(args.population), "--cohort",
           str(args.cohort), "--rounds", str(args.rounds), "--warm-cap",
           str(args.warm_cap), "--shard-size", str(args.shard_size),
           "--dim", str(args.dim), "--min-n", str(args.min_n), "--max-n",
           str(args.max_n), "--width", str(args.width), "--timeout",
           str(args.timeout)]
    if exchange:
        cmd += ["--exchange", exchange]
    cmd += list(extra)          # argparse keeps the LAST occurrence: extra
    env = dict(os.environ)      # may override --rounds etc. per case
    # workers measure host memory and the exchange; several run at once,
    # and a chip belongs to one process, so they never touch it
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    if args.host_devices:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{args.host_devices}")
    env.setdefault("PYTHONPATH", str(REPO_ROOT / "src"))
    return subprocess.Popen(cmd, env=env)


def _collect(procs, results, expect=None) -> list:
    """Wait for every worker and load its result JSON.  ``expect`` maps
    each worker to its expected return code (default 0 for all) — the
    hard-kill chaos case expects 17 from the killed rank, whose slot in
    ``results`` is then ``None`` (it died before writing a file)."""
    for i, p in enumerate(procs):
        rc = p.wait()
        want = 0 if expect is None else expect[i]
        if rc != want:
            sys.exit(f"worker {i} exited {rc} (expected {want})")
    out = []
    for path in results:
        if path is None:
            out.append(None)
            continue
        with open(path) as f:
            out.append(json.load(f))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--host", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--exchange", default="", help=argparse.SUPPRESS)
    ap.add_argument("--result", default="", help=argparse.SUPPRESS)
    ap.add_argument("--executor", default="", help=argparse.SUPPRESS)
    ap.add_argument("--crash-prob", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--corrupt-prob", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--host-crash-prob", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--ckpt", default="", help=argparse.SUPPRESS)
    ap.add_argument("--resume", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--die-at-round", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-composition cases (async under "
                         "correlated host crashes + kill-then-resume) "
                         "instead of the memory-bound cases")
    ap.add_argument("--all", dest="all_cases", action="store_true",
                    help="run the memory-bound AND the chaos cases into "
                         "one payload")
    ap.add_argument("--n-hosts", type=int, default=2,
                    help="emulated host processes for the distributed run")
    ap.add_argument("--population", type=int, default=1_000_000)
    ap.add_argument("--cohort", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--warm-cap", type=int, default=256,
                    help="GLOBAL warm cap; each host keeps cap // n_hosts")
    ap.add_argument("--shard-size", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--min-n", type=int, default=2048)
    ap.add_argument("--max-n", type=int, default=4096)
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--host-devices", type=int, default=8,
                    help="XLA host-platform devices per worker (0 = leave "
                         "XLA_FLAGS alone; workers then run the vmap route)")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_multihost.json"))
    args = ap.parse_args(argv)

    if args.worker:
        return _worker(args)

    cases: list = []
    failures: list = []
    devices = None
    if args.all_cases or not args.chaos:
        cases, failures, devices = _run_memory(args)
    if args.chaos or args.all_cases:
        ch_cases, ch_fail, ch_dev = _run_chaos(args)
        cases += ch_cases
        failures += ch_fail
        devices = devices if devices is not None else ch_dev
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}")
        return 1

    payload = {
        "task": "toy", "devices": devices,
        "backend": "cpu", "clients": args.cohort, "width": args.width,
        "population": args.population, "n_hosts": args.n_hosts,
        "dim": args.dim, "min_n": args.min_n, "max_n": args.max_n,
        "cases": cases,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")
    return 0


def _run_memory(args) -> tuple[list, list, int]:
    """The memory-bound cases (the original bench): fresh single-host
    baseline vs the n-host split, per-host warm/RSS bounds enforced."""
    with tempfile.TemporaryDirectory(prefix="repro_mh_bench_") as tmp:
        # -- single-host baseline (fresh process: clean VmHWM) -------------
        single_res = os.path.join(tmp, "single.json")
        single = _collect([_spawn(args, 0, 1, "", single_res)],
                          [single_res])[0]

        # -- the distributed run: n_hosts workers, shared exchange dir -----
        exch = os.path.join(tmp, "exchange")
        results = [os.path.join(tmp, f"host{h}.json")
                   for h in range(args.n_hosts)]
        hosts = _collect(
            [_spawn(args, h, args.n_hosts, exch, results[h])
             for h in range(args.n_hosts)], results)

    per_host_cap = max(1, args.warm_cap // args.n_hosts)
    max_rss = max(h["peak_host_rss_mb"] for h in hosts)
    print(f"\nsingle host: peak RSS {single['peak_host_rss_mb']:.0f} MB, "
          f"peak_warm {single['peak_warm']} (cap {args.warm_cap})")
    print(f"{args.n_hosts} hosts:     max peak RSS {max_rss:.0f} MB, "
          f"peak_warm {[h['peak_warm'] for h in hosts]} "
          f"(per-host cap {per_host_cap})")

    failures = []
    if single["peak_warm"] > args.warm_cap:
        failures.append(f"single-host peak_warm {single['peak_warm']} "
                        f"exceeded cap {args.warm_cap}")
    for h in hosts:
        # the synchronous round pins only the owned cohort slice, which
        # the per-host cap dominates at these settings — no excursion slack
        if h["peak_warm"] > per_host_cap:
            failures.append(f"{h['host']} peak_warm {h['peak_warm']} "
                            f"exceeded per-host cap {per_host_cap}")
        if h["final_acc"] != single["final_acc"]:
            failures.append(f"{h['host']} final_acc {h['final_acc']} != "
                            f"single-host {single['final_acc']} — the "
                            f"placement changed the numbers")
    if not max_rss < single["peak_host_rss_mb"] * 0.95:
        failures.append(f"max per-host RSS {max_rss:.0f} MB is not "
                        f"measurably below the single-host "
                        f"{single['peak_host_rss_mb']:.0f} MB")

    common = {"algo": "fedavg", "executor": single["executor"], "epochs": 1,
              "precompute": False, "population": args.population,
              "cohort": args.cohort, "rounds": args.rounds,
              "warm_cap": args.warm_cap}
    cases = ([dict(common, **single)]
             + [dict(common, **h) for h in hosts]
             + [dict(common, host="max_over_hosts",
                     peak_host_rss_mb=max_rss,
                     peak_warm=max(h["peak_warm"] for h in hosts),
                     rss_ratio_vs_single=round(
                         max_rss / single["peak_host_rss_mb"], 4))])
    return cases, failures, single["devices"]


def _newest_checkpoint_round(ckpt_dir: str, host: int):
    """Newest per-host checkpoint round on disk, or None."""
    import re

    pat = re.compile(rf"^state_host{host:03d}_(\d{{6}})\.npz$")
    rounds = [int(m.group(1)) for name in os.listdir(ckpt_dir)
              if (m := pat.match(name))]
    return max(rounds) if rounds else None


def _run_chaos(args) -> tuple[list, list, int]:
    """The fault-composition cases: the async executor under correlated
    host-crash + client faults (throughput while the fleet degrades and
    recovers), then a mid-run hard kill of host 0 followed by a
    coordinated resume of the whole topology (rounds replayed past the
    agreed restore point)."""
    fault_flags = ["--crash-prob", "0.05", "--corrupt-prob", "0.05",
                   "--host-crash-prob", "0.2"]
    common = {"algo": "fedavg", "epochs": 1, "precompute": False,
              "population": args.population, "cohort": args.cohort,
              "rounds": args.rounds, "warm_cap": args.warm_cap}
    cases: list = []
    failures: list = []
    with tempfile.TemporaryDirectory(prefix="repro_mh_chaos_") as tmp:
        # -- async under correlated faults: throughput while degraded ------
        exch = os.path.join(tmp, "exchange_async")
        results = [os.path.join(tmp, f"async_host{h}.json")
                   for h in range(args.n_hosts)]
        hosts = _collect(
            [_spawn(args, h, args.n_hosts, exch, results[h],
                    extra=["--executor", "async", *fault_flags])
             for h in range(args.n_hosts)], results)
        if len({h["final_acc"] for h in hosts}) != 1:
            failures.append(f"async chaos hosts diverged: final_acc "
                            f"{[h['final_acc'] for h in hosts]}")
        if not any(h["f_host_crashes"] for h in hosts):
            failures.append("async chaos run drew zero host crashes — the "
                            "correlated-fault path was not exercised")
        ups = min(h["async_client_updates_per_sec"] for h in hosts)
        print(f"\nasync chaos ({args.n_hosts} hosts): {ups:.1f} client "
              f"updates/s (min over hosts), "
              f"{hosts[0]['f_host_crashes']} correlated host crashes, "
              f"{hosts[0]['f_retries']} retries")
        cases += [dict(common, **h) for h in hosts]
        cases.append(dict(common, host="chaos_async_min",
                          executor="async", faults=hosts[0]["faults"],
                          async_client_updates_per_sec=ups))

        # -- mid-run hard kill of host 0, then coordinated resume ----------
        rounds = max(4, args.rounds)
        die_at = max(2, rounds // 2)
        exch2 = os.path.join(tmp, "exchange_kill")
        ckpt = os.path.join(tmp, "ckpt")
        # survivors burn one full exchange timeout detecting the dead
        # peer (crash-stop detection); cap it — rounds complete in
        # seconds, so 60s is still far above live-host skew
        base = ["--executor", "async", "--ckpt", ckpt,
                "--rounds", str(rounds),
                "--timeout", str(min(args.timeout, 60.0)), *fault_flags]
        kill_results = [None] + [os.path.join(tmp, f"kill_host{h}.json")
                                 for h in range(1, args.n_hosts)]
        procs = [_spawn(args, 0, args.n_hosts, exch2,
                        os.path.join(tmp, "kill_host0.json"),
                        extra=[*base, "--die-at-round", str(die_at)])]
        procs += [_spawn(args, h, args.n_hosts, exch2, kill_results[h],
                         extra=base) for h in range(1, args.n_hosts)]
        _collect(procs, kill_results,
                 expect=[17] + [0] * (args.n_hosts - 1))
        restore = _newest_checkpoint_round(ckpt, host=0)
        if restore is None:
            failures.append("killed host left no loadable checkpoint — "
                            "nothing to resume from")
            return cases, failures, hosts[0]["devices"]

        resume_results = [os.path.join(tmp, f"resume_host{h}.json")
                          for h in range(args.n_hosts)]
        resumed = _collect(
            [_spawn(args, h, args.n_hosts, exch2, resume_results[h],
                    extra=[*base, "--resume"])
             for h in range(args.n_hosts)], resume_results)
        if len({r["final_acc"] for r in resumed}) != 1:
            failures.append(f"resumed hosts diverged: final_acc "
                            f"{[r['final_acc'] for r in resumed]}")
        recovery = rounds - restore
        print(f"kill-resume: host 0 killed at round {die_at}, topology "
              f"restored from round {restore} -> {recovery} of {rounds} "
              f"rounds replayed")
        cases.append(dict(common, host="chaos_kill_resume",
                          executor="async", rounds=rounds,
                          faults=resumed[0]["faults"],
                          final_acc=resumed[0]["final_acc"],
                          host_crash_recovery_rounds=recovery))
    return cases, failures, hosts[0]["devices"]


if __name__ == "__main__":
    raise SystemExit(main())
