"""End-to-end federated LM training driver (deliverable (b) backbone).

Trains an assigned-architecture (reduced or full) causal LM with FedGKD
across K clients holding non-IID synthetic token streams (per-client Markov
sources).  Two execution paths:

  serial    one client at a time (any device count) — the FL-simulation path
  sharded   clients mapped onto the mesh "data" axis via shard_map: every
            client's local epoch runs concurrently with NO cross-client
            collectives; aggregation is a single weighted psum — the
            jax-native image of the paper's MPI round (DESIGN.md §4)

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --smoke --rounds 5 --clients 4 --algo fedgkd
"""
from __future__ import annotations

import argparse
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.core.distillation import ensemble_average
from repro.core.server import ModelBuffer, weighted_average
from repro.data.synthetic import lm_token_batches
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer
from repro.optim import sgd
from repro.sharding import make_mesh_compat, shard_map_compat

Params = Any


# ---------------------------------------------------------------------------
# data: per-client non-IID token streams
# ---------------------------------------------------------------------------

def client_batches(cfg, n_clients: int, batches_per_round: int, batch: int,
                   seq: int, seed: int = 0) -> np.ndarray:
    """(K, B_per_round, batch, seq) int32 — each client draws from its own
    Markov source (label-distribution skew analogue for LM data)."""
    out = np.empty((n_clients, batches_per_round, batch, seq), np.int32)
    for k in range(n_clients):
        rng = np.random.default_rng(seed * 1000 + k)
        for b in range(batches_per_round):
            out[k, b] = lm_token_batches(rng, batch, seq, cfg.vocab_size)
    return out


def eval_ppl(params, cfg, tokens: jnp.ndarray) -> float:
    logits, _ = transformer.forward(params, cfg, tokens[:, :-1])
    ce = steps_lib.lm_cross_entropy(logits, tokens[:, 1:])
    return float(jnp.exp(ce))


# ---------------------------------------------------------------------------
# serial FL round
# ---------------------------------------------------------------------------

def make_round_clock(n_clients: int, *, straggler_frac: float,
                     straggler_slowdown: float, seed: int):
    """Optional simulated system-heterogeneity clock for the LM drivers.

    Returns ``None`` (no simulation) or a callable mapping per-round work
    (batches per client) to the SYNCHRONOUS barrier cost — the virtual
    seconds until the slowest client of the round finishes
    (``repro.core.systemsim`` speeds, straggler profile).  The drivers
    attach it as ``sim_seconds`` per round so a straggler tail's cost on
    the round barrier is measurable before real heterogeneous hardware
    exists; the single-host FL loop's ``executor="async"`` path is the
    remedy those numbers motivate.
    """
    if straggler_frac <= 0.0:
        return None
    from repro.core import systemsim
    sim = systemsim.SystemSim(
        n_clients,
        systemsim.SpeedProfile(kind="straggler",
                               straggler_frac=straggler_frac,
                               straggler_slowdown=straggler_slowdown),
        rng=systemsim.derive_rng(seed))
    return lambda work: max(sim.duration(k, work) for k in range(n_clients))


def run_serial(cfg, *, rounds: int, n_clients: int, batches_per_round: int,
               batch: int, seq: int, algo: str = "fedgkd", gamma: float = 0.2,
               buffer_m: int = 3, lr: float = 0.1, seed: int = 0,
               verbose: bool = True, straggler_frac: float = 0.0,
               straggler_slowdown: float = 4.0) -> dict:
    round_clock = make_round_clock(n_clients, straggler_frac=straggler_frac,
                                   straggler_slowdown=straggler_slowdown,
                                   seed=seed)
    opt = sgd(momentum=0.9)
    kd_mode = "teacher" if algo == "fedgkd" else "none"
    step = jax.jit(steps_lib.make_train_step(cfg, opt, kd_mode=kd_mode,
                                             gamma=gamma, lr=lr))
    global_params = transformer.init(jax.random.PRNGKey(seed), cfg)
    buf = ModelBuffer(buffer_m)
    buf.push(global_params)
    eval_toks = jnp.asarray(lm_token_batches(
        np.random.default_rng(9999), 8, seq, cfg.vocab_size))
    history = []
    for t in range(rounds):
        t0 = time.time()
        data = client_batches(cfg, n_clients, batches_per_round, batch, seq,
                              seed=seed + t)
        teacher = ensemble_average(buf.models) if kd_mode == "teacher" else ()
        new_params, weights = [], []
        for k in range(n_clients):
            p = global_params
            o = opt.init(p)
            for b in range(batches_per_round):
                bt = jnp.asarray(data[k, b])
                batch_dict = {"tokens": bt[:, :-1], "labels": bt[:, 1:]}
                p, o, metrics = step(p, teacher, o, batch_dict)
            new_params.append(p)
            weights.append(float(batch * batches_per_round))
        global_params = weighted_average(new_params, weights)
        buf.push(global_params)
        ppl = eval_ppl(global_params, cfg, eval_toks)
        rec = {"round": t + 1, "ppl": ppl, "loss": float(metrics["loss"]),
               "seconds": time.time() - t0}
        if round_clock is not None:
            rec["sim_seconds"] = round_clock(batches_per_round)
        history.append(rec)
        if verbose:
            print(f"[{algo}] round {t+1}/{rounds} ppl={ppl:.2f} "
                  f"loss={float(metrics['loss']):.4f} "
                  f"({history[-1]['seconds']:.1f}s)", flush=True)
    return {"history": history, "params": global_params}


# ---------------------------------------------------------------------------
# shard_map client-parallel FL round
# ---------------------------------------------------------------------------

def make_parallel_round(cfg, mesh: Mesh, *, gamma: float = 0.2,
                        lr: float = 0.1, kd_mode: str = "teacher"):
    """FL round as ONE jitted program: clients sharded over the mesh's
    "clients" axis; local scans have no collectives; aggregation = psum."""
    opt = sgd(momentum=0.9)
    step = steps_lib.make_train_step(cfg, opt, kd_mode=kd_mode, gamma=gamma,
                                     lr=lr)

    def per_client(params, teacher, tokens):
        # tokens: (B_per_round, batch, seq) for THIS client
        opt_state = opt.init(params)

        def body(carry, bt):
            p, o = carry
            batch_dict = {"tokens": bt[:, :-1], "labels": bt[:, 1:]}
            p, o, m = step(p, teacher, o, batch_dict)
            return (p, o), m["loss"]

        (params, _), losses = jax.lax.scan(body, (params, opt_state), tokens)
        return params, jnp.mean(losses)

    def round_fn(global_params, teacher, tokens, weights):
        # leading axis = clients (sharded): run my shard's client, aggregate
        params = jax.tree_util.tree_map(lambda x: x[0], global_params)
        teacher_l = jax.tree_util.tree_map(lambda x: x[0], teacher) \
            if kd_mode == "teacher" else ()
        new_params, loss = per_client(params, teacher_l, tokens[0])
        w = weights[0]
        total = jax.lax.psum(w, "clients")
        agg = jax.tree_util.tree_map(
            lambda p: jax.lax.psum(p * (w / total), "clients").astype(p.dtype),
            new_params)
        loss_mean = jax.lax.pmean(loss, "clients")
        return (jax.tree_util.tree_map(lambda x: x[None], agg),
                loss_mean[None])

    spec_c = P("clients")
    pspec = jax.tree_util.tree_map(lambda _: spec_c, jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), cfg)))
    in_specs = (pspec, pspec if kd_mode == "teacher" else P(),
                spec_c, spec_c)
    out_specs = (pspec, spec_c)
    fn = shard_map_compat(round_fn, mesh, in_specs=in_specs,
                          out_specs=out_specs)
    return jax.jit(fn)


def run_sharded(cfg, *, rounds: int, batches_per_round: int, batch: int,
                seq: int, gamma: float = 0.2, buffer_m: int = 3,
                lr: float = 0.1, seed: int = 0, algo: str = "fedgkd",
                verbose: bool = True, straggler_frac: float = 0.0,
                straggler_slowdown: float = 4.0) -> dict:
    """Clients == host devices; one shard_map program per round."""
    n_clients = len(jax.devices())
    round_clock = make_round_clock(n_clients, straggler_frac=straggler_frac,
                                   straggler_slowdown=straggler_slowdown,
                                   seed=seed)
    mesh = make_mesh_compat((n_clients,), ("clients",))
    kd_mode = "teacher" if algo == "fedgkd" else "none"
    round_fn = make_parallel_round(cfg, mesh, gamma=gamma, lr=lr,
                                   kd_mode=kd_mode)
    global_params = transformer.init(jax.random.PRNGKey(seed), cfg)
    buf = ModelBuffer(buffer_m)
    buf.push(global_params)
    eval_toks = jnp.asarray(lm_token_batches(
        np.random.default_rng(9999), 8, seq, cfg.vocab_size))
    bcast = lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape), tree)
    history = []
    for t in range(rounds):
        t0 = time.time()
        data = jnp.asarray(client_batches(cfg, n_clients, batches_per_round,
                                          batch, seq, seed=seed + t))
        teacher = ensemble_average(buf.models) if kd_mode == "teacher" else ()
        weights = jnp.ones((n_clients,), jnp.float32)
        stacked, loss = round_fn(bcast(global_params),
                                 bcast(teacher) if kd_mode == "teacher" else (),
                                 data, weights)
        global_params = jax.tree_util.tree_map(lambda x: x[0], stacked)
        buf.push(global_params)
        ppl = eval_ppl(global_params, cfg, eval_toks)
        rec = {"round": t + 1, "ppl": ppl, "loss": float(loss[0]),
               "seconds": time.time() - t0}
        if round_clock is not None:
            rec["sim_seconds"] = round_clock(batches_per_round)
        history.append(rec)
        if verbose:
            print(f"[{algo}/sharded] round {t+1}/{rounds} ppl={ppl:.2f} "
                  f"loss={float(loss[0]):.4f}", flush=True)
    return {"history": history, "params": global_params}


def run_fl_task(args) -> int:
    """Single-host FL-loop preset path: ``--fl-task cifar10`` etc.

    Drives ``fl_loop.run_federated`` on a paper task (``model=resnet8`` for
    the CIFAR tasks) under the chosen executor.  ``--executor vmap`` on the
    conv backbones runs the client-batched grouped-conv round body
    (``kernels.grouped_conv``) — the historical "batched-weight convs lower
    poorly under vmap" caveat no longer applies; the route that actually
    ran is printed from the telemetry.
    """
    import dataclasses

    from repro.configs.paper import PAPER_TASKS, scaled
    from repro.core import algorithms as algo_lib
    from repro.core import fl_loop

    task = scaled(PAPER_TASKS[args.fl_task], scale=args.fl_scale,
                  rounds=args.rounds, local_epochs=1)
    if args.clients:
        task = dataclasses.replace(
            task, n_clients=max(task.n_clients, args.clients),
            participation=args.clients / max(task.n_clients, args.clients))
    data = fl_loop.make_federated_data(task, alpha=10.0, seed=0, n_test=256)
    h = fl_loop.run_federated(
        task, algo_lib.make(args.algo, gamma=args.gamma,
                            buffer_m=args.buffer_m),
        data, seed=0, width=args.fl_width, executor=args.executor,
        max_batches_per_client=args.batches_per_round, verbose=True)
    print(f"model={task.model} executor={args.executor} "
          f"round_body={h.telemetry.get('round_body', '-')} "
          f"final_acc={h.final_acc:.4f}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--fl-task", default=None, choices=sorted(
                        ("cifar10", "cifar100", "tiny-imagenet", "toy")),
                    help="run the single-host FL loop on a paper task "
                         "(model=resnet8/resnet50/mlp per task) instead of "
                         "the LM driver; --executor selects the route")
    ap.add_argument("--executor", default="auto",
                    help="FL-task executor: auto/sequential/vmap/shard_map/"
                         "async (vmap on the conv backbones uses the "
                         "client-batched grouped-conv body)")
    ap.add_argument("--fl-scale", type=float, default=0.02,
                    help="FL-task dataset scale (CPU-sized default)")
    ap.add_argument("--fl-width", type=int, default=16,
                    help="resnet8 width for --fl-task")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--algo", choices=("fedavg", "fedgkd"), default="fedgkd")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batches-per-round", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--gamma", type=float, default=0.2)
    ap.add_argument("--buffer-m", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--sharded", action="store_true",
                    help="clients-in-parallel via shard_map")
    ap.add_argument("--straggler-frac", type=float, default=0.0,
                    help="simulate a straggler tail: this fraction of "
                         "clients runs --straggler-slowdown x slower and "
                         "each round reports sim_seconds (the synchronous "
                         "barrier cost on the virtual clock)")
    ap.add_argument("--straggler-slowdown", type=float, default=4.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.fl_task:
        return run_fl_task(args)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    kw = dict(rounds=args.rounds, batches_per_round=args.batches_per_round,
              batch=args.batch, seq=args.seq, gamma=args.gamma,
              buffer_m=args.buffer_m, lr=args.lr, algo=args.algo,
              straggler_frac=args.straggler_frac,
              straggler_slowdown=args.straggler_slowdown)
    if args.sharded:
        out = run_sharded(cfg, **kw)
    else:
        out = run_serial(cfg, n_clients=args.clients, **kw)
    print("final ppl:", out["history"][-1]["ppl"])
    if args.straggler_frac > 0:
        total = sum(r["sim_seconds"] for r in out["history"])
        print(f"simulated round-barrier time: {total:.1f} virtual s over "
              f"{args.rounds} rounds (straggler tail "
              f"{args.straggler_frac:.0%} at "
              f"{args.straggler_slowdown:g}x slowdown)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
