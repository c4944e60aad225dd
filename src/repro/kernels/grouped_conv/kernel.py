"""Client-batched convolution forward — Pallas TPU kernel.

One grid step computes one (client, Cout tile, example) output plane as an
implicit-im2col blocked matmul: the kh*kw filter taps are accumulated as

    acc (OH*Ws, Cin) @ w[k, i, j, :, tile] (Cin, tc)

on the MXU, every tap a contiguous row window of the VMEM input block — the
patch matrix is never materialized in HBM.  Every client convolves with ITS
OWN filters, which is exactly the computation the batched executors need and
the thing a vmapped ``conv_general_dilated`` lowers badly.

Layout (see the Pallas guide's tiling constraints): ``grouped_conv_fwd``
rewrites the padded input outside the kernel so that no tap needs a strided
or 3-D slice, both of which the chip's compiler refuses inside a kernel:

* phase split — a stride-``s`` conv reads ``x[s*r + i, s*c + j]``, which is
  element ``(r + i//s, c + j//s)`` of phase ``(i % s, j % s)``, the
  sub-grid ``x[i % s::s, j % s::s]``.  Splitting the input into its ``s*s``
  phases turns every tap into a stride-1 read;
* flattening — each phase is flattened row-major to ``(Hs*Ws, Cin)``, so
  tap ``(i, j)`` of the whole output plane is ONE row window starting at
  ``(i//s)*Ws + j//s``.  The output is computed on the phase grid's full
  width ``Ws``; the ``Ws - OW`` wrap-around columns per row are garbage and
  are cropped after the call.

Channel axes are padded to 128 lanes by ``ops.py``, so the dot shapes are
lane-aligned.  ``Cout`` is tiled by 128 on a grid axis so the
largest weight block — resnet50's 3x3 512->512 — fits the scoped VMEM
limit double-buffered; the example axis is innermost, so each weight tile
is fetched once per (client, tile).

The backward runs through the pure-JAX formulas in ``ref.py`` (grouped
transposed conv for dx, shift-GEMM for dw) via the custom VJP in
``ops.py``; a fused backward kernel is a follow-up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SUBLANES = 8
_BLOCK_COUT = 128   # Cout tile: one MXU-width block of output channels


def _grouped_conv_fwd_kernel(x_ref, w_ref, out_ref, *, stride: int, kh: int,
                             kw: int, ws: int, rows: int):
    """One (client, Cout tile, example): all taps of the flattened plane."""
    acc = jnp.zeros((rows, out_ref.shape[-1]), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            phase = (i % stride) * stride + j % stride
            start = (i // stride) * ws + j // stride
            patch = x_ref[0, 0, phase, pl.ds(start, rows), :]  # (rows, Cin)
            acc = acc + jnp.dot(patch, w_ref[0, i, j],
                                preferred_element_type=jnp.float32)
    out_ref[0, 0] = acc.astype(out_ref.dtype)


def _phase_flatten(x: jax.Array, stride: int, length: int) -> jax.Array:
    """(K, N, Hp, Wp, C) -> (K, N, s*s, length, C): the stride phases of
    the plane, each flattened row-major and zero-padded to ``length``."""
    k, n, hp, wp, c = x.shape
    s = stride
    hs, ws = -(-hp // s), -(-wp // s)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, hs * s - hp), (0, ws * s - wp),
                    (0, 0)))
    x = x.reshape(k, n, hs, s, ws, s, c).transpose(0, 1, 3, 5, 2, 4, 6)
    x = x.reshape(k, n, s * s, hs * ws, c)
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, length - hs * ws), (0, 0)))


def grouped_conv_fwd(x_padded: jax.Array, w: jax.Array, *, stride: int,
                     oh: int, ow: int, interpret: bool = False) -> jax.Array:
    """(K, N, Hp, Wp, Cin) ⊛ (K, kh, kw, Cin, Cout) -> (K, N, OH, OW, Cout).

    ``x_padded`` already carries the SAME/VALID spatial padding; channel
    axes should be lane-padded by the caller (``ops.py`` does both), so
    that 128 divides ``Cout`` or exceeds it (one tile).
    """
    k, n, hp, wp, cin = x_padded.shape
    kh, kw, cout = w.shape[1], w.shape[2], w.shape[4]
    s = stride
    ws = -(-wp // s)
    rows = -(-(oh * ws) // _SUBLANES) * _SUBLANES
    last = ((kh - 1) // s) * ws + (kw - 1) // s + rows
    length = max(-(-hp // s) * ws, last)
    length = -(-length // _SUBLANES) * _SUBLANES
    xf = _phase_flatten(x_padded, s, length)
    tc = min(_BLOCK_COUT, cout)
    if cout % tc:
        raise ValueError(f"Cout={cout} is not a multiple of {_BLOCK_COUT}")
    kernel = functools.partial(_grouped_conv_fwd_kernel, stride=s, kh=kh,
                               kw=kw, ws=ws, rows=rows)
    out = pl.pallas_call(
        kernel,
        grid=(k, cout // tc, n),
        in_specs=[
            pl.BlockSpec((1, 1, s * s, length, cin),
                         lambda a, c, b: (a, b, 0, 0, 0)),
            pl.BlockSpec((1, kh, kw, cin, tc),
                         lambda a, c, b: (a, 0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, tc),
                               lambda a, c, b: (a, b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((k, n, rows, cout), x_padded.dtype),
        interpret=interpret,
    )(xf, w)
    return out[:, :, :oh * ws].reshape(k, n, oh, ws, cout)[:, :, :, :ow]
