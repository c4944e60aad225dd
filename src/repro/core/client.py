"""Client side: the local update loop (Alg. 1, CLIENTUPDATE).

Each sampled client runs E epochs of minibatch SGD on
    F_k(w) + <algorithm-specific regularizer>(w; payload, client_state)

The local pass is expressed as a ``lax.scan`` over the steps' batch picks
``(S, B)`` (rows of the client's full shard, gathered on the device one
step at a time) with two masks:

    example mask (S, B)   zero-weight for examples padded onto a ragged
                          batch — they contribute nothing to loss/grads
    step mask    (S,)     False for steps padded onto a client with fewer
                          batches than its neighbours — the whole step is
                          an identity on (params, opt_state)

and an optional per-step ``aux`` pytree: round-constant tensors gathered
from the algorithm's ``precompute_aux`` stage (teacher logits etc. — see
``repro.core.executor``), leaves shaped ``(S, B, ...)``.  ``()`` (the empty
pytree) means "no precompute" and is delivered to the loss as ``aux=None``.

The executors in ``repro.core.executor`` vmap the SAME function over a
stacked client axis (VmapExecutor) or inside a shard_map shard
(ShardMapExecutor); the sequential route runs ``make_step`` per batch.  ``loss_fn`` comes from the algorithm and must be
pure pytree-in/pytree-out: ``loss(params, payload, client_state, x, y,
mask=None, aux=None) -> (scalar, metrics_dict)``.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.optim import Optimizer, apply_updates


def _aux_or_none(aux: Any) -> Any:
    """Normalize the executor convention: the empty pytree means no aux."""
    return None if isinstance(aux, tuple) and len(aux) == 0 else aux


def make_step(loss_fn: Callable, opt: Optimizer, jit: bool = True) -> Callable:
    """One masked SGD step.

    ``loss_fn(params, payload, client_state, x, y, mask, aux) -> (loss,
    metrics)``.  Returns ``step(params, opt_state, payload, client_state,
    x, y, mask, aux, lr)``; pass ``aux=()`` when there is no precompute.
    """

    def step(params, opt_state, payload, client_state, x, y, mask, aux, lr):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, payload, client_state, x, y, mask, _aux_or_none(aux))
        updates, opt_state = opt.update(grads, opt_state, params, lr)
        return apply_updates(params, updates), opt_state, loss, metrics

    return jax.jit(step) if jit else step


def _rows(fx: jax.Array, picks: jax.Array) -> jax.Array:
    """Per-client row gather: ``fx`` (K, N, ...) at ``picks`` (K, B) ->
    (K, B, ...)."""
    return jax.vmap(lambda f, p: f[p])(fx, picks)


def make_local_update(loss_fn: Callable, opt: Optimizer) -> Callable:
    """Build the scan-based client pass.

    Returns ``local_update(params, payload, client_state, fx, fy, picks,
    ex_mask, aux, step_mask, lr, *, x_shape=None) -> (new_params,
    mean_loss)``.  ``fx``/``fy`` are the client's full shard (N, ...) and
    labels (N,); ``picks`` (S, B) holds the shard rows of every step's
    batch, so each step gathers its own (B, ...) batch on the device and
    no (S, B, ...) stack ever exists.  ``x_shape`` is one example's shape
    where ``fx`` stores examples flattened (``None``: ``fx.shape[1:]``).
    ``aux`` is the per-step precompute pytree (leaves ``(S, B, ...)``) or
    ``()``.  Masked-out steps leave params and optimizer state untouched
    (so a padded client is bit-identical to one trained on its real steps
    only); masked-out examples are zero-weighted inside the loss.
    """
    step = make_step(loss_fn, opt, jit=False)

    def local_update(params: Any, payload: Any, client_state: Any,
                     fx: jax.Array, fy: jax.Array, picks: jax.Array,
                     ex_mask: jax.Array, aux: Any, step_mask: jax.Array, lr,
                     *, x_shape=None) -> tuple[Any, jax.Array]:
        shape = tuple(fx.shape[1:] if x_shape is None else x_shape)
        opt_state = opt.init(params)

        def body(carry, batch):
            p, o = carry
            pk, m, aux_b, live = batch
            x = fx[pk].reshape(pk.shape + shape)
            p2, o2, loss, _ = step(p, o, payload, client_state, x, fy[pk], m,
                                   aux_b, lr)
            keep = lambda new, old: jnp.where(live, new, old)
            p = jax.tree_util.tree_map(keep, p2, p)
            o = jax.tree_util.tree_map(keep, o2, o)
            return (p, o), jnp.where(live, loss, 0.0)

        (params, _), losses = jax.lax.scan(
            body, (params, opt_state), (picks, ex_mask, aux, step_mask))
        denom = jnp.maximum(1.0, jnp.sum(step_mask.astype(jnp.float32)))
        return params, jnp.sum(losses) / denom

    return local_update


def make_batched_local_update(batched_loss_fn: Callable, opt: Optimizer,
                              unroll_limit: int = 8) -> Callable:
    """The whole-cohort client pass for CLIENT-BATCHED models.

    Same call signature and outputs as ``vmap(make_local_update(...))`` —
    ``(global_params, payload, states_stacked, fx (K, N, ...), fy (K, N),
    picks (K, S, B), ex_mask, aux, step_mask (K, S), lr, *, x_shape) ->
    (params (K, ...), mean_loss (K,))`` — but instead of vmapping the
    per-client scan it broadcasts the global params to a stacked
    ``(K, ...)`` pytree and drives ``batched_loss_fn`` (one fused
    forward+backward over the cohort; conv backbones route through
    ``kernels.grouped_conv``).  Each step gathers the cohort's (K, B, ...)
    batch from the full shards by its picks, on the device, and reshapes
    it to ``x_shape`` (``None``: ``fx.shape[2:]``), so shards may be kept
    flattened, lane-dense, in memory.  Per-client masking semantics are
    identical: a client's padded step leaves ITS params and opt state
    untouched; padded examples are zero-weighted in the loss.

    Rounds with at most ``unroll_limit`` steps run as an unrolled step
    loop: on CPU, XLA executes a ``lax.scan`` over bodies this size
    drastically slower than the identical unrolled program (measured ~19x
    on resnet8 — the while-loop body misses the fusion/threading the
    straight-line program gets), and the benchmark round counts sit well
    under the limit.  Longer rounds fall back to ``lax.scan`` to bound
    compile time.
    """

    def step(params, opt_state, payload, states, x, y, m, aux_b, lr):
        (_, per), grads = jax.value_and_grad(
            batched_loss_fn, has_aux=True)(params, payload, states, x, y, m,
                                           _aux_or_none(aux_b))
        # the optimizer update IS vmapped (cheap elementwise pytree math, no
        # model ops): scalar state leaves — Adam's step count — stay
        # per-client (K,) exactly as in the vmapped round body, so the
        # per-client keep-mask below can gate every leaf
        updates, opt_state = jax.vmap(
            lambda g, o, p: opt.update(g, o, p, lr))(grads, opt_state,
                                                     params)
        return apply_updates(params, updates), opt_state, per

    def local_update(global_params: Any, payload: Any, states: Any,
                     fx: jax.Array, fy: jax.Array, picks: jax.Array,
                     ex_mask: jax.Array, aux: Any, step_mask: jax.Array, lr,
                     *, x_shape=None):
        shape = tuple(fx.shape[2:] if x_shape is None else x_shape)
        k, s = picks.shape[0], picks.shape[1]
        params = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (k,) + l.shape), global_params)
        opt_state = jax.vmap(opt.init)(params)

        def body(carry, batch):
            p, o = carry
            pk, m, aux_b, live = batch
            x = _rows(fx, pk).reshape(pk.shape + shape)
            p2, o2, per = step(p, o, payload, states, x, _rows(fy, pk), m,
                               aux_b, lr)
            keep = lambda new, old: jnp.where(
                live.reshape((k,) + (1,) * (new.ndim - 1)), new, old)
            p = jax.tree_util.tree_map(keep, p2, p)
            o = jax.tree_util.tree_map(keep, o2, o)
            return (p, o), jnp.where(live, per, 0.0)

        # step-major views: leaves (K, S, ...) -> (S, K, ...)
        swap = lambda l: jnp.swapaxes(l, 0, 1)
        steps = (swap(picks), swap(ex_mask),
                 jax.tree_util.tree_map(swap, aux), swap(step_mask))
        carry = (params, opt_state)
        if s <= unroll_limit:
            losses = []
            for i in range(s):
                carry, per = body(carry, jax.tree_util.tree_map(
                    lambda l: l[i], steps))
                losses.append(per)
            losses = jnp.stack(losses)                      # (S, K)
        else:
            carry, losses = jax.lax.scan(body, carry, steps)
        params = carry[0]
        denom = jnp.maximum(1.0, jnp.sum(step_mask.astype(jnp.float32), 1))
        return params, jnp.sum(losses, 0) / denom

    return local_update
