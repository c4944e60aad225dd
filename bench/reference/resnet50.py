"""The plain ResNet-50 forward of the ``resnet50-tinyimagenet``
configuration: the reference its cell compares with.

He et al. 2016, Table 1, as ``bench.reference.resnet`` builds it (a 7x7
stride-2 stem, GroupNorm of 16 channels a group, a 3x3 stride-2 max pool,
bottleneck stages [3, 4, 6, 3] with the stride of a downsampling
bottleneck on its 3x3 convolution and a 1x1 projection wherever the width
changes, a global mean pool and a dense head), from that module's own
layers.  One difference: the max pool's initial value is the Python
scalar ``-inf``.  There it is an array made inside the traced function,
and ``lax.reduce_window`` then takes its generic form, which cannot be
differentiated, so ``fedround``'s jitted local pass cannot train through
it.  A scalar gives the max pool's own form, with its gradient; the
forward's values are the same.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.resnet import STAGES, bottleneck, conv, dense, group_norm


def resnet50(params, x):
    h = jax.nn.relu(group_norm(params["gn0"], conv(params["stem"]["w"], x, 2)))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si, (_, blocks) in enumerate(STAGES):
        for bi in range(blocks):
            h = bottleneck(params[f"s{si}b{bi}"], h,
                           2 if (bi == 0 and si > 0) else 1)
    return dense(params["fc"], jnp.mean(h, axis=(1, 2)))
