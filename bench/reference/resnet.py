"""Plain ResNet forwards: the reference the benchmark compares with.

Straightforward ``jax.numpy``: one model, NHWC, ``lax.conv_general_dilated``
with SAME padding, GroupNorm of 16 channels a group (the paper's swap for
BatchNorm), ReLU, global mean pool, a dense head.  No kernels, no client
stacking.  Parameters are read by the names the program gives them, so the
reference can be handed the same initial weights.

``resnet8``: a 3x3 stem at stride 1, then three basic blocks (width w,
2w, 4w), the last two at stride 2 with a 1x1 projection on the shortcut.
``resnet50``: He et al. 2016, Table 1, with the stride of a downsampling
bottleneck on its 3x3 convolution (as torchvision's ResNet-50 has it) and a
1x1 projection wherever the width changes.

Everything is computed in ``dtype``: float32 is the reference, and a lower
type gives the control that ``correct`` has to reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5
PER_GROUP = 16


def conv(w, x, stride):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def group_norm(p, x):
    n, h, w, c = x.shape
    g = max(1, c // PER_GROUP)
    y = x.reshape(n, h, w, g, c // g)
    mean = jnp.mean(y, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=(1, 2, 4), keepdims=True)
    y = ((y - mean) / jnp.sqrt(var + EPS)).reshape(n, h, w, c)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def dense(p, x):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def basic_block(p, x, stride):
    y = jax.nn.relu(group_norm(p["gn1"], conv(p["conv1"]["w"], x, stride)))
    y = group_norm(p["gn2"], conv(p["conv2"]["w"], y, 1))
    if "proj" in p:
        x = conv(p["proj"]["w"], x, stride)
    return jax.nn.relu(x + y)


def bottleneck(p, x, stride):
    y = jax.nn.relu(group_norm(p["gn1"], conv(p["conv1"]["w"], x, 1)))
    y = jax.nn.relu(group_norm(p["gn2"], conv(p["conv2"]["w"], y, stride)))
    y = group_norm(p["gn3"], conv(p["conv3"]["w"], y, 1))
    if "proj" in p:
        x = conv(p["proj"]["w"], x, stride)
    return jax.nn.relu(x + y)


def resnet8(params, x):
    h = jax.nn.relu(group_norm(params["gn0"], conv(params["stem"]["w"], x, 1)))
    h = basic_block(params["block1"], h, 1)
    h = basic_block(params["block2"], h, 2)
    h = basic_block(params["block3"], h, 2)
    return dense(params["fc"], jnp.mean(h, axis=(1, 2)))


STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))


def resnet50(params, x):
    h = jax.nn.relu(group_norm(params["gn0"], conv(params["stem"]["w"], x, 2)))
    h = jax.lax.reduce_window(h, jnp.array(-jnp.inf, h.dtype), jax.lax.max,
                              (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si, (_, blocks) in enumerate(STAGES):
        for bi in range(blocks):
            h = bottleneck(params[f"s{si}b{bi}"], h,
                           2 if (bi == 0 and si > 0) else 1)
    return dense(params["fc"], jnp.mean(h, axis=(1, 2)))


FORWARD = {"resnet8": resnet8, "resnet50": resnet50}
