"""Public client-batched convolution with custom VJP.

``client_batched_conv(x, w, stride=, padding=)`` convolves K clients'
batches with K different filter stacks in ONE program:

    x (K, N, H, W, Cin) ⊛ w (K, kh, kw, Cin, Cout) -> (K, N, OH, OW, Cout)

Forward: the Pallas im2col-blocked-matmul kernel on TPU, the pure-JAX
grouped-conv oracle (``ref.grouped_pack_conv``) elsewhere — same selection
convention as ``kernels.kd_kl.ops`` (``use_pallas=None`` auto-detects,
``interpret=None`` auto-selects interpret mode off-TPU).

Backward: a custom VJP, because autodiff of EITHER forward is wrong-shaped
for speed — XLA expresses the rhs-gradient of a feature-grouped conv as a
``batch_group_count`` convolution that is pathologically slow on CPU
(measured ~65x on the resnet8 shapes), and the Pallas forward has no
registered gradient at all.  The VJP formulas stay block-diagonal over
clients:

    dx = feature-grouped transposed conv   (``ref.grouped_conv_dx``)
    dw = kh*kw client-batched GEMMs        (``ref.shift_gemm_dw``)

both measured at-or-better than the vmapped per-client gradients on the
CPU dev box (see ROADMAP for the per-layer table).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grouped_conv import kernel as K
from repro.kernels.grouped_conv import ref

_LANES = 128    # TPU lane width: channel axes are padded to this for the MXU


def _pad_axis(a: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _pallas_fwd(x, w, stride, padding, interpret):
    k, n, h, wd, cin = x.shape
    kh, kw, cout = w.shape[1], w.shape[2], w.shape[4]
    oh, lo_h, hi_h = ref.resolve_pads(h, kh, stride, padding)
    ow, lo_w, hi_w = ref.resolve_pads(wd, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), (0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    xp = _pad_axis(xp, 4, _LANES)
    wp = _pad_axis(_pad_axis(w, 3, _LANES), 4, _LANES)
    with jax.named_scope("grouped_conv_fwd"):
        out = K.grouped_conv_fwd(xp, wp, stride=stride, oh=oh, ow=ow,
                                 interpret=interpret)
    return out[..., :cout]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _conv(x, w, stride, padding, use_pallas, interpret):
    if use_pallas:
        return _pallas_fwd(x, w, stride, padding, interpret)
    return ref.grouped_pack_conv(x, w, stride, padding)


def _conv_fwd(x, w, stride, padding, use_pallas, interpret):
    return _conv(x, w, stride, padding, use_pallas, interpret), (x, w)


def _conv_bwd(stride, padding, use_pallas, interpret, res, dy):
    x, w = res
    dx = ref.grouped_conv_dx(dy, w, stride, x.shape[2], x.shape[3], padding)
    dw = ref.shift_gemm_dw(x, dy, stride, w.shape[1], w.shape[2], padding)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def client_batched_conv(x: jax.Array, w: jax.Array, *, stride: int = 1,
                        padding: str = "SAME",
                        use_pallas: bool | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """Per-client convolution over a stacked cohort, one fused program.

    ``use_pallas=None`` selects the Pallas kernel on TPU and the grouped
    jnp oracle elsewhere; ``interpret=None`` auto-enables interpret mode
    off-TPU (tests force ``use_pallas=True, interpret=True`` on CPU).
    Gradients flow to both ``x`` and ``w`` through the custom VJP
    regardless of the forward backend.
    """
    if x.ndim != 5 or w.ndim != 5:
        raise ValueError(
            f"client_batched_conv wants x (K, N, H, W, Cin) and w "
            f"(K, kh, kw, Cin, Cout); got {x.shape} and {w.shape}")
    if x.shape[0] != w.shape[0]:
        raise ValueError(
            f"client axes disagree: x has K={x.shape[0]}, w has "
            f"K={w.shape[0]}")
    if padding not in ("SAME", "VALID"):
        raise ValueError(
            f"padding must be 'SAME' or 'VALID', got {padding!r}")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _conv(x, w, int(stride), padding, bool(use_pallas),
                 bool(interpret))
