"""Plain federated rounds: FedAvg and FedGKD as the paper states them.

One client at a time, one jitted ``lax.scan`` over its local steps:

* the cohort: K of the clients drawn uniformly without replacement,
  ``rng.choice(n_clients, K, replace=False)``, from the run's ``numpy``
  generator at the start of each round;
* the batch order: for each sampled client, in cohort order, one
  ``permutation`` of its shard per started epoch from the run's
  ``numpy`` generator, batches of ``min(B, n)`` taken in that order, a
  short last batch filled from the front of the same permutation, until
  ``steps`` batches are drawn;
* the local step: cross-entropy over the batch, plus for FedGKD
  ``gamma / 2`` times the mean KL(teacher || student), the teacher being
  the parameter mean of the last ``buffer_m`` global models (paper Eq. 4),
  its logits computed inline on the same batch; SGD with momentum,
  weight decay added to the gradient, the momentum restarted each round;
* the server: the new global model is the mean of the clients' models
  weighted by their shard sizes; FedGKD pushes it into the buffer;
* evaluation: mean cross-entropy of the global model over the test set.

``dtype`` float32 runs at ``"highest"`` matrix precision: the reference.
A lower ``dtype`` gives the control.  ``fault`` plants a fault in the
reference put in the program's place, to read what it does to the numbers
that decide ``correct``: ``"half_batch"`` takes the loss over the first
half of each batch only.  ``test_loss`` evaluates any model, so that a
fault of the evaluation can be read from the reference's own models.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.resnet import FORWARD


def batch_order(rng: np.random.Generator, n: int, batch: int, epochs: int,
                steps: int) -> np.ndarray:
    bs = min(batch, n)
    out = []
    for _ in range(epochs):
        order = rng.permutation(n)
        for i in range(0, n, bs):
            idx = order[i:i + bs]
            if len(idx) < bs:
                idx = np.concatenate([idx, order[:bs - len(idx)]])
            out.append(idx)
            if len(out) == steps:
                return np.stack(out)
    return np.stack(out)


def _log_softmax(z):
    z = z - jax.lax.stop_gradient(jnp.max(z, axis=-1, keepdims=True))
    return z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))


def make_local(cfg: dict, algorithm: str, dtype, fault: str | None):
    forward = FORWARD[cfg["model"]]
    lr, mom, wd = cfg["lr"], cfg["momentum"], cfg["weight_decay"]
    gamma = cfg["gamma"] if algorithm == "fedgkd" else 0.0

    def loss(p, teacher, x, y):
        if fault == "half_batch":
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        logp = _log_softmax(forward(p, x))
        ce = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
        if not gamma:
            return ce
        logq = _log_softmax(jax.lax.stop_gradient(forward(teacher, x)))
        kl = jnp.sum(jnp.exp(logq) * (logq - logp), axis=-1)
        return ce + 0.5 * gamma * jnp.mean(kl)

    def local(p, teacher, xs, ys):
        def step(carry, batch):
            p, m = carry
            value, g = jax.value_and_grad(loss)(p, teacher, *batch)
            g = jax.tree.map(lambda g, w: g + wd * w, g, p)
            m = jax.tree.map(lambda m, g: mom * m + g, m, g)
            p = jax.tree.map(lambda w, m: w - lr * m, p, m)
            return (p, m), value

        m0 = jax.tree.map(jnp.zeros_like, p)
        (p, _), values = jax.lax.scan(step, (p, m0), (xs.astype(dtype), ys))
        return p, jnp.mean(values.astype(jnp.float32))

    return jax.jit(local)


@functools.lru_cache
def _summed_loss(model: str, dtype):
    forward = FORWARD[model]

    @jax.jit
    def summed(p, x, y):
        logp = _log_softmax(forward(p, x.astype(dtype)).astype(jnp.float32))
        return -jnp.sum(jnp.take_along_axis(logp, y[:, None], axis=1))

    return summed


def test_loss(cfg: dict, w, test, dtype=jnp.float32,
              block: int = 1000) -> float:
    """Mean cross-entropy of the model ``w`` over ``test`` (x, y)."""
    summed = _summed_loss(cfg["model"], dtype)
    x, y = test
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        return sum(float(summed(w, jnp.asarray(x[i:i + block]),
                                jnp.asarray(y[i:i + block].astype(np.int32))))
                   for i in range(0, len(y), block)) / len(y)


def run(cfg: dict, algorithm: str, w0, shards, test, rounds: int, *,
        run_seed: int, steps: int, dtype=jnp.float32,
        fault: str | None = None, matmul: str = "highest") -> dict:
    """``rounds`` rounds from the global model ``w0``: each round's cohort,
    client losses, client models, test loss and new global model (host
    float32).
    ``matmul`` is the float32 matrix precision (``"default"``: the chip's)."""
    precision = (jax.default_matmul_precision(matmul)
                 if dtype == jnp.float32 else contextlib.nullcontext())
    local = make_local(cfg, algorithm, dtype, fault)
    rng = np.random.default_rng(run_seed)
    cast = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)
    w = cast(w0)
    buffer = [w]
    out = {"cohorts": [], "client_loss": [], "test_loss": [], "params": [],
           "client_params": []}
    k = max(1, int(round(cfg["participation"] * cfg["n_clients"])))
    with precision:
        for _ in range(rounds):
            cohort = rng.choice(cfg["n_clients"], size=k, replace=False)
            teacher = jax.tree.map(
                lambda *ws: (sum(a.astype(jnp.float32) for a in ws)
                             / len(ws)).astype(dtype), *buffer)
            models, sizes, losses = [], [], []
            for cid in cohort:
                x, y = shards[int(cid)]
                sel = batch_order(rng, len(y), cfg["batch_size"],
                                  cfg["local_epochs"], steps)
                p, value = local(w, teacher, jnp.asarray(x[sel]),
                                 jnp.asarray(y[sel].astype(np.int32)))
                models.append(p)
                sizes.append(float(len(y)))
                losses.append(float(value))
            total = sum(sizes)
            w = jax.tree.map(
                lambda *ps: sum((s / total) * a.astype(jnp.float32)
                                for s, a in zip(sizes, ps)).astype(dtype),
                *models)
            buffer = (buffer + [w])[-cfg["buffer_m"]:]
            out["cohorts"].append([int(c) for c in cohort])
            out["client_loss"].append(losses)
            out["test_loss"].append(test_loss(cfg, w, test, dtype))
            host = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float32),
                                          t)
            out["params"].append(host(w))
            out["client_params"].append([host(p) for p in models])
    return out
