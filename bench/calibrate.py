#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --program-seeds 1,2,... \
        --control-seeds 7,8,9 [--fault half_batch --fault half_test ...]

For each program seed, one run of the cell as the benchmark runs it
(``harness.run_cell``) with a window of one round: the program's numbers
against the reference (the lower readings).  For each control seed, the
same numbers with the reference computed in bfloat16 put in the
program's place (the control), with the reference at the chip's default
matrix precision in its place (a witness of what that precision alone
reads), and with each ``--fault`` planted in the
reference put in its place: ``half_batch`` (``bench.reference.fedround``)
trains on half of every batch; ``half_test`` evaluates the reference's
models over the first half of the test set, ``stale_eval`` evaluates each
round the model it started from.  One JSON line
per reading on standard output, and with ``--out`` the same lines with
every leaf's norms appended to that file.  The benchmark's own runs never
run this.
"""
import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--out", default="",
                    help="also append every reading, with its per-leaf "
                         "norms, to this JSON-lines file")
    args = ap.parse_args()
    seeds = lambda s: [int(v) for v in s.split(",") if v]

    import jax
    import jax.numpy as jnp

    from bench import correct, data, harness, traffic
    from bench.reference import fedround
    from repro.core import fl_loop
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = harness.load_cell(args.workload)
    cfg, tr = cell["cfg"], cell["traffic"]
    def emit(leaves=None, **kw):
        print(json.dumps(kw), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(dict(kw, workload=args.workload,
                                        leaves=leaves)) + "\n")

    for seed in seeds(args.program_seeds):
        t = time.perf_counter()
        out = harness.run_cell(cfg, tr, cell["limits"], seed=seed,
                               seconds=0.0, trace=False, t0=t)
        emit(kind="program", seed=seed,
             numbers={k: v["value"] for k, v in out["checks"].items()},
             per_round=out["per_round"], losses=out["losses"],
             leaves=out["leaves"], seconds=time.perf_counter() - t)

    for seed in seeds(args.control_seeds):
        s = harness.seeds(seed)
        shards, test_x, test_y = data.make_arrays(cfg, s["data"])
        model = fl_loop.make_model(harness.paper_task(cfg),
                                   width=cfg["width"])
        w0 = jax.device_get(harness.make_weights(model, s["weights"]))
        kw = dict(shards=shards, test=(test_x, test_y),
                  rounds=tr["compare_rounds"], run_seed=s["run"],
                  steps=traffic.local_steps(cfg))
        ref = fedround.run(cfg, tr["algorithm"], w0, **kw)
        half = (test_x[:len(test_y) // 2], test_y[:len(test_y) // 2])
        evals = {
            "half_test": lambda: [fedround.test_loss(cfg, w, half)
                                  for w in ref["params"]],
            "stale_eval": lambda: [fedround.test_loss(cfg, w, (test_x, test_y))
                                   for w in [w0] + ref["params"][:-1]]}
        variants = {"control_bfloat16": dict(dtype=jnp.bfloat16),
                    "reference_default": dict(matmul="default")}
        variants.update({f"fault_{f}": dict(fault=f) for f in args.fault})
        for name, extra in variants.items():
            if extra.get("fault") in evals:
                got = dict(ref, test_loss=evals[extra["fault"]]())
            else:
                got = fedround.run(cfg, tr["algorithm"], w0, **kw, **extra)
            got = dict(got, client_params=got["client_params"][0])
            emit(kind=name, seed=seed,
                 numbers=correct.numbers(w0, got, ref),
                 per_round=dict(correct.per_round(w0, got, ref),
                                client_update=correct.client_gaps(w0, got,
                                                                  ref)),
                 leaves=correct.leaf_table(w0, got, ref),
                 losses={k: {"got": got[k], "reference": ref[k]}
                         for k in ("client_loss", "test_loss")})
    print(json.dumps({"seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
