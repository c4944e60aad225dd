"""The benchmark harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run

1. finds the cell in ``BENCHMARK.json`` and, by name, its configuration
   (``bench/configs/<config>.json``), its traffic
   (``bench/traffic/<traffic>.json``), its limits
   (``bench/cells/<cell>.json``) and its per-layer metrics
   (``bench/metrics/<metric>.py``);
2. makes the data and the initial weights from ``--seed``;
3. drives ``repro.core.fl_loop.run_federated`` once, with enough rounds
   to outlast the window, drawing its own cohorts (``bench.traffic``);
4. counts the first ``warmup_rounds`` rounds as set-up, then measures for
   ``--seconds``, taking each round's time in ``round_callback`` after
   ``jax.block_until_ready`` on the new global model, and closes the
   window at the first round boundary after ``--seconds`` (and not before
   the last round the reference follows).  In the first
   round, set-up also compiles, or loads from the persistent cache, the
   teacher precompute at every row count a cohort can pad it to, through
   programs of its own (``prime_precompute``): a round of the window that
   draws a new largest shard traces the program's precompute anew and
   loads it from the cache, as a user's run with a warm cache does, and
   never compiles;
5. with ``--trace 1``, profiles ``trace_rounds`` more rounds after the
   window and reduces the trace (``bench.trace``);
6. reads the peak device memory, lets the program's state go, and runs
   the plain reference over the first ``compare_rounds`` rounds
   (``bench.correct``);
7. prints the numbers compared beside their limits on standard error and
   one JSON line on standard output.

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics.  A run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class WindowClosed(Exception):
    """Raised from the round callback to end ``run_federated``."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """Everything one cell needs, found by name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    applies = lambda m: workload in m.get("workloads", [workload])
    return {
        "entry": entry,
        "cfg": load_json(BENCH / "configs" / f"{entry['config']}.json"),
        "traffic": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "limits": load_json(BENCH / "cells" / f"{workload}.json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def seeds(seed: int) -> dict:
    """Independent 31-bit seeds for each use, from any whole ``--seed``."""
    state = np.random.SeedSequence(seed).generate_state(4)
    return dict(zip(("data", "weights", "order", "run"),
                    (int(v) & 0x7FFFFFFF for v in state)))


def make_weights(model, seed: int):
    """Initial global weights in one jitted call on the device: He-normal
    convolutions, 1/sqrt(fan_in) dense layers (both truncated at two
    standard deviations), unit norm scales, zero biases."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def init(key):
        out = []
        for (path, leaf), k in zip(flat, jax.random.split(key, len(flat))):
            name = path[-1].key
            if name == "w":
                fan_in = math.prod(leaf.shape[:-1])
                std = math.sqrt((2.0 if leaf.ndim == 4 else 1.0) / fan_in)
                out.append(std * jax.random.truncated_normal(
                    k, -2.0, 2.0, leaf.shape, leaf.dtype))
            elif name == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            else:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return init(jax.random.key(seed))


def paper_task(cfg: dict):
    from repro.configs.paper import PaperTask

    return PaperTask(
        name=cfg["name"], kind="image", model=cfg["model"],
        num_classes=cfg["num_classes"], train_size=cfg["train_size"],
        n_clients=cfg["n_clients"], rounds=10 ** 9,
        local_epochs=cfg["local_epochs"], participation=cfg["participation"],
        batch_size=cfg["batch_size"], lr=cfg["lr"], momentum=cfg["momentum"],
        weight_decay=cfg["weight_decay"], optimizer=cfg["optimizer"],
        gamma=cfg["gamma"], buffer_m=cfg["buffer_m"],
        image_hw=cfg["image_hw"])


def make_algorithm(cfg: dict, name: str):
    from repro.core import algorithms

    if name.startswith("fedgkd"):
        return algorithms.make(name, gamma=cfg["gamma"],
                               buffer_m=cfg["buffer_m"])
    return algorithms.make(name)


def prime_precompute(ex, ctx, payload, client_data, rows: list[int],
                     steps: int) -> None:
    """Run the executor's teacher precompute and its row gather once at
    each of ``rows`` rows a client, through a copy of the round's context
    with jit caches of its own: the programs compile, or load, into the
    persistent cache alone, and the program's own jitted functions still
    trace each new shape when a round first needs it."""
    import copy

    import jax
    import jax.numpy as jnp

    own = copy.copy(ctx)
    own.jit_cache = {}
    k = len(client_data)
    feat = client_data[0].x.shape[1:]
    picks = jnp.zeros((k, steps, ctx.batch_size), jnp.int32)
    for n in rows:
        aux = ex._precompute_fn(own)(
            payload, jnp.zeros((k, n) + feat, client_data[0].x.dtype),
            jnp.zeros((k, n), jnp.int32), jnp.zeros((k, n), jnp.float32))
        jax.block_until_ready(ex._gather_fn(own)(aux, picks))


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@dataclasses.dataclass
class Run:
    """What a run measured: what the per-layer readers read."""
    cfg: dict
    traffic: dict
    chips: int
    peaks: dict
    sizes: list
    steps: int
    window_rounds: list        # round numbers in the window
    window_s: float            # untraced, from the window's first round
                               # boundary to its last
    cohorts: dict              # round -> cohort
    spans: object              # bench.spans.Spans
    window_compiles: int
    trace: object = None       # bench.trace.Trace of the traced rounds
    traced_rounds: list = dataclasses.field(default_factory=list)


def device_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(cfg: dict, traffic: dict, limits: dict, *, seed: int,
             seconds: float, trace: bool, chips: int = 1, t0: float,
             per_layer: list = (), trace_dir: Path | None = None) -> dict:
    """One run: the program's window, then the reference.  Returns the
    result line's fields (without ``device``)."""
    import jax

    from bench import correct, data, spans as spans_lib, traffic as tl
    from bench import trace as trace_lib
    from bench.reference import fedround
    from repro.core import executor as executor_lib, fl_loop
    from repro.data.pipeline import ClientData, FederatedData

    s = seeds(seed)
    sizes = data.client_sizes(cfg)
    steps = tl.local_steps(cfg)
    warmup, compare = traffic["warmup_rounds"], traffic["compare_rounds"]
    shards, test_x, test_y = data.make_arrays(cfg, s["data"])
    fed = FederatedData([ClientData(x, y) for x, y in shards], test_x,
                        test_y, np.zeros((len(shards), cfg["num_classes"])))
    drawn = tl.record_cohorts(fed)
    task = paper_task(cfg)
    algo = make_algorithm(cfg, traffic["algorithm"])
    model = fl_loop.make_model(task, algo.needs_projection_head,
                               width=cfg["width"])
    w0 = make_weights(model, s["weights"])
    w0_host = jax.device_get(w0)
    t_data = time.perf_counter()

    sp = spans_lib.Spans()
    compiles = spans_lib.Compiles().register()
    losses, evals, params, clients = {}, {}, [], []
    st = {"stamps": [], "start": None, "end": None, "trace_end": None,
          "round_span": None, "compiles0": 0, "primed": False}

    def get_executor(spec, *a, **kw):
        ex = executor_lib_get(spec, *a, **kw)
        run_round = ex.run_round

        def timed(ctx, global_params, payload, client_states, client_data,
                  *args, **kwargs):
            with sp.span("bench.run_round"):
                out = run_round(ctx, global_params, payload, client_states,
                                client_data, *args, **kwargs)
            losses[sp.round] = [float(v) for v in out.local_losses]
            if sp.round == 1:
                clients.extend(jax.device_get(u["params"])
                               for u in out.uploads)
            if ctx.has_precompute and not st["primed"]:
                prime_precompute(ex, ctx, payload, client_data,
                                 tl.cohort_maxima(cfg, sizes), steps)
                st["primed"] = True
            return out
        ex.run_round = timed
        return ex

    def evaluate(*args, **kwargs):
        with sp.span("bench.evaluate"):
            out = evaluate_orig(*args, **kwargs)
        evals[sp.round] = out[1]
        return out

    def callback(r, server, _model):
        jax.block_until_ready(server["global"])
        now = time.perf_counter()
        if st["round_span"] is not None:
            st["round_span"].__exit__(None, None, None)
            st["round_span"] = None
        st["stamps"].append(now)
        st.setdefault("counts", []).append((compiles.compiles,
                                            compiles.loads))
        sp.round = r + 1
        if r <= compare:
            params.append(jax.device_get(server["global"]))
        if r == warmup:
            st["start"], st["compiles0"] = now, compiles.count
        elif st["start"] is not None and st["end"] is None and (
                now - st["start"] >= seconds) and r >= compare:
            st["end"], st["compiles"] = r, compiles.count - st["compiles0"]
            if not trace:
                raise WindowClosed
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            st["trace_end"] = r + traffic["trace_rounds"]
        if st["trace_end"] is not None:
            if r == st["trace_end"]:
                jax.profiler.stop_trace()
                raise WindowClosed
            st["round_span"] = jax.profiler.TraceAnnotation(
                trace_lib.ROUND_SPAN)
            st["round_span"].__enter__()

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 1, 0
    opts.enable_hlo_proto = False
    executor_lib_get = executor_lib.get_executor
    evaluate_orig = fl_loop.evaluate
    algo.server_update = sp.wrap("bench.server_update", algo.server_update)
    init_model = dataclasses.replace(model, init=lambda rng: w0)
    try:
        with spans_lib.patched(fl_loop, "make_model",
                               lambda *a, **kw: init_model), \
                spans_lib.patched(executor_lib, "get_executor",
                                  get_executor), \
                spans_lib.patched(fl_loop, "evaluate", evaluate):
            fl_loop.run_federated(
                task, algo, fed, rounds=10 ** 9, seed=s["run"],
                eval_every=1, max_batches_per_client=steps,
                round_callback=callback,
                executor=traffic["executor"])
    except WindowClosed:
        pass
    del model, init_model, w0, algo, fed
    stamps = st["stamps"]
    first, last = warmup + 1, st["end"]
    window = list(range(first, last + 1))
    window_s = stamps[last - 1] - stamps[warmup - 1]
    cohorts = {r: drawn[r - 1] for r in range(1, len(stamps) + 1)}
    examples = sum(tl.trained_examples(cfg, steps, sizes, cohorts[r])
                   for r in window)
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:chips])
    gc.collect()

    e2e = {
        "setup_s": {"value": st["start"] - t0, "unit": "s"},
        "train_examples_per_s": {"value": examples / window_s,
                                 "unit": "examples/s"},
    }
    failed = sum(1 for r in window
                 if not all(math.isfinite(v) for v in losses.get(r, [0.0])))
    out = {"attempted": len(window), "failed": failed, "memory": memory,
           "metrics": e2e}
    if trace:
        path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        red = trace_lib.reduce(path, chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        kind = jax.devices()[0].device_kind
        run = Run(cfg=cfg, traffic=traffic, chips=chips,
                  peaks=device_peaks(kind), sizes=sizes, steps=steps,
                  window_rounds=window,
                  window_s=window_s, cohorts=cohorts, spans=sp,
                  window_compiles=st["compiles"], trace=red,
                  traced_rounds=list(range(last + 1, st["trace_end"] + 1)))
        metrics = {}
        for m in per_layer:
            value = load_metric(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        out["busy_s"], out["window_s"] = red.busy_s(), red.window_s
        log(f"traced: {len(run.traced_rounds)} rounds in {red.window_s:.3f}"
            f" s, {red.window_s / len(run.traced_rounds):.3f} s a round "
            f"against {window_s / len(window):.3f} s untraced; device busy "
            f"{red.busy_s():.3f} s")
        gaps: dict = {}
        for sec, label in red.idle_gaps():
            gaps[label] = gaps.get(label, 0.0) + sec
        out["breakdown"] = {
            "device_ops": red.top_ops(10),
            "idle_gaps": [[k, v] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:10]]}

    t_ref = time.perf_counter()
    prog = {"client_loss": [losses[r] for r in range(1, compare + 1)],
            "test_loss": [evals[r] for r in range(1, compare + 1)],
            "params": params[:compare], "client_params": clients}
    ref = fedround.run(cfg, traffic["algorithm"], w0_host, shards,
                       (test_x, test_y), compare, run_seed=s["run"],
                       steps=steps)
    drawn_first = [[int(c) for c in cohorts[r]] for r in range(1, compare + 1)]
    if ref["cohorts"] != drawn_first:
        log(f"cohorts differ: program {drawn_first}, reference "
            f"{ref['cohorts']}")
    nums = correct.numbers(w0_host, prog, ref)
    out["per_round"] = correct.per_round(w0_host, prog, ref)
    out["per_round"]["client_update"] = correct.client_gaps(w0_host, prog,
                                                            ref)
    out["leaves"] = correct.leaf_table(w0_host, prog, ref)
    out["losses"] = {k: {"program": prog[k], "reference": ref[k]}
                     for k in ("client_loss", "test_loss")}
    log(f"per round: {json.dumps(out['per_round'])}")
    log("losses: " + json.dumps(out["losses"]))
    new_max, seen = [], set()
    for r in range(1, last + 1):
        top = max(sizes[int(c)] for c in cohorts[r])
        new_max.append(top not in seen)
        seen.add(top)
    counts = st["counts"]
    log("rounds (s / compiles / loads / new largest shard): " + ", ".join(
        f"{b - a:.3f}/{cb[0] - ca[0]}/{cb[1] - ca[1]}/{int(new)}"
        for a, b, ca, cb, new in zip([t_data] + stamps, stamps,
                                     [(0, 0)] + counts, counts, new_max)))
    log(f"times: data+weights {t_data - t0:.2f} s, set-up "
        f"{st['start'] - t0:.2f} s, window {window_s:.2f} s over "
        f"{len(window)} rounds, reference {time.perf_counter() - t_ref:.2f} s")
    out["correct"], out["checks"] = correct.verdict(nums, limits)
    return out


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    chips = cell["entry"]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"bench: needs {chips} TPU chip(s); jax found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    trace_dir = BENCH / ".traces" / f"{args.workload}-{args.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = run_cell(cell["cfg"], cell["traffic"], cell["limits"],
                   seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), chips=chips, t0=t0,
                   per_layer=cell["per_layer"], trace_dir=trace_dir)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory"]}
    if args.trace:
        device["busy_s"], device["window_s"] = out["busy_s"], out["window_s"]
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device}
    if args.trace:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0
