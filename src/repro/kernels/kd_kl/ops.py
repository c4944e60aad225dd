"""Public jit'd wrapper for the fused KD-KL loss with custom VJP.

``kd_kl_loss(teacher_logits, student_logits)`` accepts any (..., V) shapes,
flattens leading dims, pads rows/vocab to block multiples (padded vocab
columns are −inf'd so they contribute nothing), and returns per-row KL with
gradients flowing ONLY to the student (teacher is a frozen ensemble in
FedGKD, Eq. 4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.kd_kl import kernel as K
from repro.kernels.kd_kl import ref

_PAD = -1e30


def _pad2(x, br, bv, fill):
    t, v = x.shape
    pt, pv = (-t) % br, (-v) % bv
    if pt or pv:
        x = jnp.pad(x, ((0, pt), (0, pv)), constant_values=fill)
    return x


def _fwd_impl(teacher_logits, student_logits, temperature, block_rows,
              block_vocab, interpret):
    lt = _pad2(teacher_logits, block_rows, block_vocab, _PAD)
    ls = _pad2(student_logits, block_rows, block_vocab, _PAD)
    with jax.named_scope("kd_kl_fwd"):
        kl, lse_t, lse_s = K.kd_kl_fwd(
            lt, ls, temperature=temperature, block_rows=block_rows,
            block_vocab=block_vocab, interpret=interpret)
    return kl[: teacher_logits.shape[0]], lse_t, lse_s


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _kd_kl_rows(teacher_logits, student_logits, temperature, block_rows,
                block_vocab, interpret):
    return _fwd_impl(teacher_logits, student_logits, temperature, block_rows,
                     block_vocab, interpret)[0]


def _fwd(teacher_logits, student_logits, temperature, block_rows,
         block_vocab, interpret):
    # the (padded-length) row logsumexps fall out of the forward kernel's
    # online-softmax scratch — saving them as residuals lets the backward
    # rebuild p_T/p_S without re-reducing the vocab axis
    out, lse_t, lse_s = _fwd_impl(teacher_logits, student_logits, temperature,
                                  block_rows, block_vocab, interpret)
    return out, (teacher_logits, student_logits, lse_t, lse_s)


def _bwd(temperature, block_rows, block_vocab, interpret, res, g):
    lt, ls, lse_t, lse_s = res
    t, v = lt.shape
    ltp = _pad2(lt, block_rows, block_vocab, _PAD)
    lsp = _pad2(ls, block_rows, block_vocab, _PAD)
    gp = jnp.pad(g, (0, (-t) % block_rows))
    with jax.named_scope("kd_kl_bwd"):
        dls = K.kd_kl_bwd(ltp, lsp, lse_t, lse_s, gp.astype(jnp.float32),
                          temperature=temperature, block_rows=block_rows,
                          block_vocab=block_vocab, interpret=interpret)
    # temperature² from fwd's /(inv²) cancels one 1/temp of d(l/temp): net ·temp
    dls = dls[:t, :v] * temperature * temperature
    return jnp.zeros_like(lt), dls.astype(ls.dtype)


_kd_kl_rows.defvjp(_fwd, _bwd)


def kd_kl_loss(teacher_logits: jax.Array, student_logits: jax.Array, *,
               temperature: float = 1.0, block_rows: int = 256,
               block_vocab: int = 1024, interpret: bool | None = None,
               use_pallas: bool = True) -> jax.Array:
    """Per-example KL(p_T‖p_S)·temp² over the last axis; leading dims kept.

    ``use_pallas=False`` falls back to the jnp oracle (CPU training path).
    ``interpret=None`` auto-selects interpret mode off-TPU.
    """
    shape = teacher_logits.shape
    assert shape == student_logits.shape
    if not use_pallas:
        # stop_gradient keeps the fallback's VJP identical to the kernel's
        # custom VJP (teacher gradient is zero on BOTH backends)
        return ref.kd_kl_rowwise(jax.lax.stop_gradient(teacher_logits),
                                 student_logits, temperature)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lt = teacher_logits.reshape(-1, shape[-1])
    ls = student_logits.reshape(-1, shape[-1])
    out = _kd_kl_rows(lt, ls, temperature, block_rows, block_vocab, interpret)
    return out.reshape(shape[:-1])
