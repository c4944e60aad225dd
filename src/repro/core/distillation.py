"""Knowledge-distillation losses and global-model ensembling (paper Eq. 3-5).

The KD regularizer is ``(γ/2)·E_x[ KL( h(w_teacher; x) ‖ h(w; x) ) ]`` —
teacher distribution first (forward KL), matching Eq. (3).  ``γ_m`` weights
for FedGKD-VOTE follow the paper's softmax-of-validation-loss rule.

``kl_divergence`` (and therefore ``kd_loss_kl``, the KD term in every hot
loss) executes through the fused Pallas kernel
``repro.kernels.kd_kl.ops.kd_kl_loss`` on TPU — one custom-VJP kernel pass
instead of three materialized softmaxes.  Off-TPU it runs the pure-jnp
oracle (identical math); gradients flow ONLY to the student either way,
which matches every call site (teachers are frozen payload constants).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.kd_kl import ops as _kd_ops


def kl_divergence(teacher_logits: jax.Array, student_logits: jax.Array,
                  temperature: float = 1.0,
                  use_pallas: bool | None = None) -> jax.Array:
    """Per-example KL(p_T ‖ p_S)·T². Shapes (..., C) -> (...).

    ``use_pallas=None`` auto-selects the fused Pallas kernel on TPU and the
    jnp oracle elsewhere; pass True/False to force a path (tests).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    return _kd_ops.kd_kl_loss(teacher_logits, student_logits,
                              temperature=temperature, use_pallas=use_pallas)


def masked_mean(values: jax.Array, mask: jax.Array | None) -> jax.Array:
    """Weighted mean over ``values`` (any shape); ``mask is None`` == plain
    mean.  Zero-weight entries contribute nothing to value or gradient, so
    padded examples in a batched (vmap) client step are exact no-ops."""
    if mask is None:
        return jnp.mean(values)
    w = mask.astype(jnp.float32)
    return jnp.sum(values * w) / jnp.maximum(1.0, jnp.sum(w))


def masked_mean_per_client(values: jax.Array, mask: jax.Array | None) -> jax.Array:
    """Row of ``masked_mean``s over a stacked cohort: values ``(K, B)`` ->
    ``(K,)``.  Client k's entry equals ``masked_mean(values[k], mask[k])``
    exactly, so a sum over the K axis is the batched executors' total loss
    whose gradient w.r.t. client-stacked params is the per-client
    gradients (parameters are disjoint across clients)."""
    if mask is None:
        return jnp.mean(values, axis=-1)
    w = mask.astype(jnp.float32)
    return jnp.sum(values * w, axis=-1) / jnp.maximum(1.0, jnp.sum(w, axis=-1))


def cross_entropy_per_client(logits: jax.Array, labels: jax.Array,
                             ignore_index: int = -1,
                             mask: jax.Array | None = None) -> jax.Array:
    """Per-client masked-mean CE: logits ``(K, B, C)`` -> ``(K,)``; each
    entry matches ``cross_entropy(logits[k], labels[k], mask=mask[k])``
    to the op (same log-softmax, same masked sum, negated last)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = (labels != ignore_index).astype(jnp.float32)
    if mask is not None:
        valid = valid * mask.astype(jnp.float32)
    safe = jnp.where(labels != ignore_index, labels, 0)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll * valid, axis=-1) / jnp.maximum(
        1.0, jnp.sum(valid, axis=-1))


def param_sq_dist_per_client(stacked, anchor) -> jax.Array:
    """‖w_k − anchor‖² per client: leaves ``(K, ...)`` against the shared
    anchor ``(...)`` -> ``(K,)`` (FedProx/FedDyn proximal terms on the
    client-stacked route)."""
    total = 0.0
    for s, a in zip(jax.tree_util.tree_leaves(stacked),
                    jax.tree_util.tree_leaves(anchor)):
        d = s.astype(jnp.float32) - a.astype(jnp.float32)[None]
        total = total + jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
    return total


def kd_loss_kl(teacher_logits, student_logits, gamma: float,
               temperature: float = 1.0, mask=None,
               use_pallas: bool | None = None) -> jax.Array:
    """Paper Eq.(3) KD term: (γ/2)·mean KL (fused kernel on TPU)."""
    return 0.5 * gamma * masked_mean(
        kl_divergence(teacher_logits, student_logits, temperature,
                      use_pallas=use_pallas), mask)


def kd_loss_mse(teacher_logits, student_logits, gamma: float,
                mask=None) -> jax.Array:
    """Table 9 ablation: MSE over logits instead of KL."""
    d = (teacher_logits.astype(jnp.float32)
         - student_logits.astype(jnp.float32))
    return 0.5 * gamma * masked_mean(jnp.sum(jnp.square(d), axis=-1), mask)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore_index: int = -1, mask: jax.Array | None = None) -> jax.Array:
    """Mean CE with optional ignore label (used to mask frontend positions)
    and optional per-example weights (executor padding mask)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = (labels != ignore_index).astype(jnp.float32)
    if mask is not None:
        valid = valid * mask.astype(jnp.float32)
    safe = jnp.where(labels != ignore_index, labels, 0)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll * valid) / jnp.maximum(1.0, jnp.sum(valid))


# ---------------------------------------------------------------------------
# global-model ensembling (server side)
# ---------------------------------------------------------------------------

def ensemble_average(params_list: list) -> dict:
    """FedGKD fused teacher: plain weight-space mean of the buffered models
    (Polyak-style, Eq. w̄_t = (1/M)·Σ w_{t-m+1})."""
    m = len(params_list)
    return jax.tree_util.tree_map(
        lambda *xs: sum(x.astype(jnp.float32) for x in xs) / m, *params_list)


def vote_coefficients(val_losses: list[float], lam: float = 0.1,
                      beta: float | None = None) -> list[float]:
    """FedGKD-VOTE γ_m/2 = λ · softmax(-L_m/β); β defaults to 1/M (paper)."""
    m = len(val_losses)
    beta = beta if beta is not None else 1.0 / m
    l = jnp.asarray(val_losses, jnp.float32)
    w = jax.nn.softmax(-l / beta)
    return [2.0 * lam * float(x) for x in w]  # returns γ_m (the full coefficient)


def param_sq_dist(a, b) -> jax.Array:
    """‖a − b‖² over pytrees (FedProx proximal term)."""
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))
