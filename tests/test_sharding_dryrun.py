"""Sharding rules + a reduced-mesh dry-run (lower+compile) in a subprocess
with a forced host device count (the main pytest process stays at 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs, sharding as sh
from repro.models import transformer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_specs_col_row_assignment():
    cfg = configs.get_smoke_config("phi4-mini-3.8b")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    specs = sh.param_specs(params, cfg)
    # stacked layer weights: leading None + col/row split
    assert specs["seg0"]["attn"]["wq"]["w"] == P(None, None, "model")
    assert specs["seg0"]["attn"]["wo"]["w"] == P(None, "model", None)
    assert specs["seg0"]["mlp"]["gate"]["w"] == P(None, None, "model")
    assert specs["seg0"]["mlp"]["down"]["w"] == P(None, "model", None)
    assert specs["embed"]["table"] == P("model", None)
    assert specs["final_norm"]["scale"] == P(None)


def test_param_specs_fsdp_adds_data_axis():
    cfg = configs.get_smoke_config("phi4-mini-3.8b")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    specs = sh.param_specs(params, cfg, fsdp=True)
    assert specs["seg0"]["attn"]["wq"]["w"] == P(None, "data", "model")
    assert specs["embed"]["table"] == P("model", "data")


def test_moe_expert_parallel_spec():
    cfg = configs.get_smoke_config("mixtral-8x7b")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    specs = sh.param_specs(params, cfg)
    assert specs["seg0"]["moe"]["gate"][0] if False else True
    moe = specs["seg0"]["moe"]
    assert moe["gate"] == P(None, "model", None, None)   # (L, E, D, F)
    assert moe["down"] == P(None, "model", None, None)
    assert moe["router"]["w"] == P(None, None, None)


def test_make_mesh_compat_matches_make_mesh():
    """The repo's mesh helper builds the mesh jax.make_mesh would, axis
    names and device shape alike."""
    m = sh.make_mesh_compat((1,), ("clients",))
    assert m.axis_names == ("clients",)
    assert m.devices.shape == (1,)
    m2 = sh.make_mesh_compat((1, 1), ("data", "model"))
    assert m2.axis_names == ("data", "model")
    assert m2.devices.shape == jax.make_mesh((1, 1), ("a", "b")).devices.shape


def test_mesh_helpers_yield_auto_axes():
    """jax.make_mesh defaults to Explicit axes, under which slicing the
    executor's client-sharded outputs raises ShardingTypeError; every mesh
    the repo builds has Auto axes."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_clients_mesh, make_host_mesh
    meshes = [sh.make_mesh_compat((1, 1), ("data", "model")),
              make_clients_mesh(), make_host_mesh()]
    for m in meshes:
        assert m.axis_types == (AxisType.Auto,) * len(m.axis_names)


def test_make_clients_mesh_spans_all_devices():
    from repro.launch.mesh import make_clients_mesh
    mesh = make_clients_mesh()
    assert mesh.axis_names == ("clients",)
    assert mesh.devices.size == len(jax.devices())
    explicit = make_clients_mesh(1)
    assert explicit.devices.size == 1


def test_fit_specs_drops_nondivisible():
    mesh = jax.make_mesh((1,), ("model",))
    spec = sh.fit_specs(P("model"), jax.ShapeDtypeStruct((7,), jnp.float32),
                        mesh)
    assert spec == P("model")  # axis size 1 divides everything
    mesh2 = jax.make_mesh((1, 1), ("data", "model"))
    spec = sh.fit_specs(P(("data", "model"), None),
                        jax.ShapeDtypeStruct((3, 4), jnp.float32), mesh2)
    assert spec == P(("data", "model"), None)


_DRYRUN_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import warnings; warnings.filterwarnings("ignore")
    import jax, json
    from repro.launch import dryrun_lib
    dryrun_lib.make_production_mesh = lambda multi_pod=False: (
        jax.make_mesh((2,2,4), ("pod","data","model")) if multi_pod
        else jax.make_mesh((4,4), ("data","model")))
    results = []
    for arch, shape, multi in %s:
        r = dryrun_lib.run_dryrun(arch, shape, multi_pod=multi)
        results.append({"arch": arch, "shape": shape, "ok": r.ok,
                        "err": r.error, "flops": r.flops})
    print("JSON:" + json.dumps(results))
""")


def _run_subprocess(pairs):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _DRYRUN_SNIPPET % repr(pairs)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("JSON:")][-1]
    return json.loads(line[5:])


@pytest.mark.slow
def test_dryrun_reduced_mesh_lowers_and_compiles():
    """3 representative (arch × shape) pairs + one multi-pod, on a 16-device
    stand-in mesh: lower().compile() must succeed and report nonzero FLOPs."""
    pairs = [("phi4-mini-3.8b", "decode_32k", False),
             ("mixtral-8x7b", "train_4k", False),
             ("mamba2-2.7b", "long_500k", False),
             ("phi4-mini-3.8b", "train_4k", True)]
    for r in _run_subprocess(pairs):
        assert r["ok"], (r["arch"], r["shape"], r["err"])
        assert r["flops"] > 0


@pytest.mark.slow
def test_sharded_fl_driver_runs():
    """shard_map clients-parallel FL round on 4 host devices."""
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "phi4-mini-3.8b", "--smoke", "--rounds", "1",
         "--batches-per-round", "2", "--batch", "2", "--seq", "16",
         "--sharded"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "final ppl" in out.stdout
