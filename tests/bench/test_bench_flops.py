"""The benchmark's FLOP and byte functions (bench/flops)."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench.flops import grouped_conv, kd_kl, resnet  # noqa: E402
from bench.trace import Kernel, parse_kernel  # noqa: E402
from repro.models import resnet as program  # noqa: E402

R8 = dict(model="resnet8", image_hw=32, width=16, channels=3,
          num_classes=10)
R50 = dict(model="resnet50", image_hw=64, width=64, channels=3,
           num_classes=200)
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("cfg", [R8, R50], ids=["resnet8", "resnet50"])
def test_forward_flops_match_xla_cost_analysis(cfg):
    """XLA counts every operation of the forward, the model count only
    the convolutions' and the dense layer's multiply-adds: XLA's count is
    larger by the GroupNorms, ReLUs, residual adds and pooling, which are
    under 6% of the total at these widths (3.3% and 1.4% measured)."""
    if cfg["model"] == "resnet8":
        init = lambda: program.resnet8_init(jax.random.PRNGKey(0), 10,
                                            width=16)
        apply = program.resnet8_apply
    else:
        init = lambda: program.resnet50_init(jax.random.PRNGKey(0), 200)
        apply = program.resnet50_apply
    x = jax.ShapeDtypeStruct((1, cfg["image_hw"], cfg["image_hw"], 3),
                             jnp.float32)
    xla = jax.jit(apply).lower(jax.eval_shape(init), x).cost_analysis()
    ours = resnet.forward_flops(cfg)
    assert ours <= xla["flops"] <= 1.06 * ours


def test_resnet_layer_lists():
    assert [lay.name for lay in resnet.convs(R8)] == [
        "stem", "block1.conv1", "block1.conv2", "block2.conv1",
        "block2.conv2", "block2.proj", "block3.conv1", "block3.conv2",
        "block3.proj"]
    assert len(resnet.convs(R50)) == 1 + 16 * 3 + 4
    assert resnet.layers(R50)[-1].cin == 2048


def test_same_padding_taps():
    # 3x3 stride 1 over 32: the two border rows see 2 taps, the rest 3
    assert resnet.Layer("c", 32, 3, 1, 1, 1).taps() == 32 * 3 - 2
    # 3x3 stride 2 over 32 -> 16 outputs; SAME pads one at the end only
    assert resnet.Layer("c", 32, 3, 2, 1, 1).taps() == 16 * 3 - 1
    assert resnet.Layer("c", 32, 1, 2, 1, 1).taps() == 16


def test_grouped_conv_cost_at_the_cell_shapes():
    """ResNet-8's stem at K=4 clients x B=64: the conv's own operations
    and bytes, true Cin=3, no lane padding."""
    stem = resnet.convs(R8)[0]
    taps = 32 * 3 - 2
    assert stem.flops(256) == 2 * 256 * taps * taps * 3 * 16
    x, y, w = 256 * 32 * 32 * 3, 256 * 32 * 32 * 16, 4 * 9 * 3 * 16
    assert stem.bytes(64, clients=4) == 4 * (x + y + w)
    least = grouped_conv.least_seconds(stem, 4, 64, PEAKS)
    assert least == pytest.approx(4 * (x + y + w) / 819e9)   # bytes bound


def test_kd_kl_cost_at_the_cell_shape():
    fwd = kd_kl.least_seconds(256, 10, False, PEAKS)
    bwd = kd_kl.least_seconds(256, 10, True, PEAKS)
    assert fwd == pytest.approx(4 * (2 * 2560 + 3 * 256) / 819e9)
    assert bwd == pytest.approx(4 * (3 * 2560 + 3 * 256) / 819e9)


# HLO text of kernel calls as the chip's trace shows them (ResNet-8 cell)
CONV_S1 = ('%jvp__.72 = f32[4,64,1088,128]{3,2,1,0:T(8,128)} custom-call('
           'f32[4,64,1,1160,128]{4,3,2,1,0:T(8,128)} %pad.461, '
           'f32[4,3,3,128,128]{4,3,2,1,0:T(8,128)S(1)} %pad.462), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints='
           '{f32[4,64,1,1160,128]{4,3,2,1,0}, f32[4,3,3,128,128]{4,3,2,1,0}}')
CONV_S2 = ('%jvp__.73 = f32[4,64,272,128]{3,2,1,0:T(8,128)S(1)} custom-call('
           'f32[4,64,4,296,128]{4,3,2,1,0:T(8,128)} %pad.465, '
           'f32[4,3,3,128,128]{4,3,2,1,0:T(8,128)S(1)} %pad.466), '
           'custom_call_target="tpu_custom_call"')
PROJ = ('%jvp__.75 = f32[4,64,256,128]{3,2,1,0:T(8,128)S(1)} custom-call('
        'f32[4,64,4,256,128]{4,3,2,1,0:T(8,128)} %bitcast.1422, '
        'f32[4,1,1,128,128]{4,3,2,1,0:T(8,128)S(1)} %pad.473), '
        'custom_call_target="tpu_custom_call"')


def test_parse_kernel_shapes():
    k = parse_kernel(CONV_S2, 1e-3)
    assert k.operands == [(4, 64, 4, 296, 128), (4, 3, 3, 128, 128)]
    assert k.results == [(4, 64, 272, 128)]
    assert grouped_conv.is_call(k) and grouped_conv.signature(k) == (3, 2)
    assert grouped_conv.signature(parse_kernel(PROJ, 0)) == (1, 2)
    assert not kd_kl.is_forward(k) and not kd_kl.is_backward(k)
    fwd = Kernel([(256, 1024), (256, 1024)], [(256,), (256,), (256,)], 0)
    bwd = Kernel([(256, 1024), (256, 1024), (256,), (256,), (256,)],
                 [(256, 1024)], 0)
    assert kd_kl.is_forward(fwd) and kd_kl.is_backward(bwd)


def test_grouped_conv_roofline_matches_calls_to_layers():
    """One forward: 5 stride-1 3x3 convs, 2 stride-2 3x3, 2 projections;
    the share is the layers' least times over the calls' durations."""
    calls = ([parse_kernel(CONV_S1, 1e-3)] * 5
             + [parse_kernel(CONV_S2, 1e-3)] * 2
             + [parse_kernel(PROJ, 1e-3)] * 2)
    want = sum(grouped_conv.least_seconds(lay, 4, 64, PEAKS)
               for lay in resnet.convs(R8)) / 9e-3
    got = grouped_conv.roofline(R8, calls, 9, PEAKS)
    assert got == pytest.approx(want)
    assert 0 < got < 1
    # a call count the model does not explain reads nothing
    assert grouped_conv.roofline(R8, calls[:-1], 8, PEAKS) is None
    assert grouped_conv.roofline(R8, calls, 18, PEAKS) is None
    assert grouped_conv.roofline(R8, [], 0, PEAKS) is None
