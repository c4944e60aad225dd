"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing here runs on a chip: each test compiles a kernel at its real
width for a described (not attached) ``v5e:2x2`` topology, with
``interpret=False``, and checks that the compiled program holds the
kernel (``tpu_custom_call``).  That catches what interpret mode cannot —
slices the chip's compiler refuses, blocks that overflow the scoped VMEM
limit — before any chip time is spent.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.grouped_conv import ops as conv_ops
from repro.kernels.kd_kl import ops as kd_ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("rows_vocab", [(256, 10), (256, 200)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_kd_kl_compiles(one_chip, rows_vocab, grad):
    def fwd(t, s):
        return kd_ops.kd_kl_loss(t, s, interpret=False)

    fn = (jax.grad(lambda t, s: jnp.sum(fwd(t, s)), argnums=1) if grad
          else fwd)
    text = _compiled_text(fn, rows_vocab, rows_vocab, sharding=one_chip)
    assert "tpu_custom_call" in text


CONV_SHAPES = {
    # name: (x (K, N, H, W, Cin), w (K, kh, kw, Cin, Cout), stride) at K=4,
    # B=64 — ResNet-8 at 32x32 and ResNet-50 at 64x64
    "r8_stem": ((4, 64, 32, 32, 3), (4, 3, 3, 3, 16), 1),
    "r8_3x3_s2": ((4, 64, 32, 32, 16), (4, 3, 3, 16, 32), 2),
    "r8_1x1_proj_s2": ((4, 64, 32, 32, 16), (4, 1, 1, 16, 32), 2),
    "r50_7x7_s2_stem": ((4, 64, 64, 64, 3), (4, 7, 7, 3, 64), 2),
    "r50_3x3_512": ((4, 64, 2, 2, 512), (4, 3, 3, 512, 512), 1),
    "r50_3x3_512_s2": ((4, 64, 4, 4, 512), (4, 3, 3, 512, 512), 2),
}


@pytest.mark.parametrize("vjp", [False, True], ids=["fwd", "vjp"])
@pytest.mark.parametrize("name", sorted(CONV_SHAPES))
def test_client_batched_conv_compiles(one_chip, name, vjp):
    xs, ws, stride = CONV_SHAPES[name]

    def fwd(x, w):
        return conv_ops.client_batched_conv(x, w, stride=stride,
                                            use_pallas=True, interpret=False)

    fn = (jax.grad(lambda x, w: jnp.sum(fwd(x, w) ** 2), argnums=(0, 1))
          if vjp else fwd)
    text = _compiled_text(fn, xs, ws, sharding=one_chip)
    assert "tpu_custom_call" in text
