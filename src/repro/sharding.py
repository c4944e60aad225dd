"""Sharding rules: parameter/activation PartitionSpecs for the mesh axes.

Mesh axes
    "model"          tensor/expert parallel (16-way per pod)
    "data"           batch / federated-client parallel (16-way per pod)
    "pod"            cross-pod data parallel (multi-pod dry-run)

Strategy (Megatron-style TP + optional ZeRO/FSDP over "data"):
    * column-parallel:  attention q/k/v, MLP gate/up, SSM in_proj  -> out dim
      on "model"
    * row-parallel:     attention wo, MLP down, SSM out_proj       -> in dim
      on "model"
    * vocab-parallel:   embedding table / output head              -> vocab
      on "model"
    * expert-parallel:  MoE expert stacks                          -> E on
      "model"
    * head-parallel:    SSD per-head params (A, D, dt_bias)        -> H on
      "model" (SSD is head-independent, so the scan shards cleanly)
    * fsdp=True additionally shards the largest replicated dim of every
      ≥2D weight over "data" (param + optimizer state) — required for the
      biggest assigned archs (deepseek-v3-671b does not fit TP-only).

Stacked (scan-over-layers) params carry a leading layer axis -> spec gets a
leading None.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def data_axes(mesh: Mesh) -> tuple:
    """The data-parallel axis (grouped with 'pod' when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def make_mesh_compat(shape: tuple[int, ...], axis_names: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax's own default is ``Explicit`` axes, under which plain indexing of a
    sharded array (``l[:k]`` on the executor's client-sharded outputs)
    raises ``ShardingTypeError``.  This repo shards with ``shard_map`` and
    ``NamedSharding`` placement, which is what ``Auto`` axes mean.  Every
    mesh in this repo (production pods, host test meshes, the executor's
    ``("clients",)`` mesh) goes through here.  A mesh smaller than the
    visible device set takes the first ``prod(shape)`` devices.
    """
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_array_from_process_local_data_compat(sharding: NamedSharding,
                                              local_data,
                                              global_shape: "tuple | None"
                                              = None):
    """``jax.make_array_from_process_local_data``: the multi-process
    cohort-assembly primitive.  Each host contributes the slice its devices
    own and jax stitches the global sharded array; single-process it is
    ``jax.device_put`` onto the sharding."""
    return jax.make_array_from_process_local_data(sharding, local_data,
                                                  global_shape)


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off.

    Every caller in this repo wants ``check_vma=False``: weights enter
    replicated but are consumed per-shard.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

_COL_KEYS = ("wq", "wk", "wv", "wq_a", "wq_b", "wkv_a", "wkv_b",
             "gate", "up", "in_proj", "fc1", "head", "proj")
_ROW_KEYS = ("wo", "down", "out_proj", "fc2")


def _spec_for(path: tuple[str, ...], shape: tuple[int, ...],
              stacked: bool, fsdp: bool) -> P:
    """PartitionSpec for one weight, by its param-tree path."""
    names = [p for p in path]
    leaf = names[-1]            # 'w' | 'b' | 'scale' | 'table' | tensor name
    parent = names[-2] if len(names) >= 2 else ""

    def with_stack(spec_dims: list):
        dims = ([None] + spec_dims) if stacked else spec_dims
        return P(*dims)

    ndim = len(shape) - (1 if stacked else 0)

    # embeddings: vocab-parallel
    if leaf == "table":
        return with_stack(["model", "data" if fsdp else None])

    # MoE expert stacks (E, D, F)/(E, F, D): expert-parallel on E
    if parent == "moe" or (leaf in ("gate", "up", "down") and ndim == 3):
        return with_stack(["model", "data" if fsdp else None, None])

    # conv weights (resnet / mamba conv): replicate K, shard channels
    if leaf == "conv_w":
        return with_stack([None, "model"])
    if leaf == "conv_b":
        return with_stack(["model"])
    if leaf in ("A_log", "D", "dt_bias"):
        return with_stack(["model"])

    if leaf == "b":             # bias of a col-parallel layer
        if parent in _COL_KEYS:
            return with_stack(["model"])
        return with_stack([None])

    if leaf == "w" and ndim == 2:
        if parent in _COL_KEYS:
            return with_stack(["data" if fsdp else None, "model"])
        if parent in _ROW_KEYS:
            return with_stack(["model", "data" if fsdp else None])
        if parent == "router":  # small, replicated
            return with_stack([None, None])
        # default 2D: col-parallel
        return with_stack(["data" if fsdp else None, "model"])

    # norms / scalars / small vectors: replicated
    return with_stack([None] * ndim)


def param_specs(params: Any, cfg: ModelConfig, *, fsdp: bool = False) -> Any:
    """Pytree of PartitionSpecs matching ``params``."""
    def spec(path, leaf):
        keys = tuple(_path_key(p) for p in path)
        stacked = bool(keys) and (keys[0].startswith("seg") or keys[0] == "enc")
        s = _spec_for(keys, leaf.shape, stacked, fsdp)
        return _validate(s, leaf.shape)

    return jax.tree_util.tree_map_with_path(spec, params)


def _path_key(p) -> str:
    return str(getattr(p, "key", getattr(p, "idx", p)))


def _validate(spec: P, shape: tuple[int, ...]) -> P:
    """Drop axis assignments that don't divide the dim (e.g. kv_heads=1 MQA
    projections smaller than the model axis, tiny vocab in smoke configs).
    XLA would replicate-with-padding; explicit None keeps the HLO clean."""
    # NOTE: divisibility depends on mesh axis sizes; checked at apply time
    return spec


def fit_specs(specs: Any, arrays: Any, mesh: Mesh) -> Any:
    """Drop axis assignments whose mesh size doesn't divide the dim (e.g.
    global_batch=1 on a 16-way data axis, MQA kv=1 head projections)."""
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(spec_leaf, arr):
        dims = list(spec_leaf) + [None] * (arr.ndim - len(spec_leaf))
        out = []
        for d, name in zip(arr.shape, dims):
            if name is None:
                out.append(None)
                continue
            size = (int(np.prod([axis_size[a] for a in name]))
                    if isinstance(name, tuple) else axis_size.get(name, 1))
            out.append(name if d % size == 0 else None)
        return P(*out)

    return jax.tree_util.tree_map(fix, specs, arrays,
                                  is_leaf=lambda x: isinstance(x, P))


def specs_with_mesh(params: Any, cfg: ModelConfig, mesh: Mesh, *,
                    fsdp: bool = False) -> Any:
    """param_specs + per-dim divisibility check against the actual mesh."""
    return fit_specs(param_specs(params, cfg, fsdp=fsdp), params, mesh)


# ---------------------------------------------------------------------------
# activation / batch specs
# ---------------------------------------------------------------------------

def batch_specs(batch_specs_tree: Any, mesh: Mesh) -> Any:
    """Shard the leading (batch) dim of every input over the data axes."""
    dp = data_axes(mesh)
    dp = dp[0] if len(dp) == 1 else dp

    def spec(x):
        return P(dp, *([None] * (len(x.shape) - 1)))

    return jax.tree_util.tree_map(spec, batch_specs_tree)


def cache_specs(cache_tree: Any, mesh: Mesh) -> Any:
    """Decode caches: stacked (L, B, ...) KV/SSM buffers -> batch on data.

    Cache leaves are (layers, batch, ...) or scalars (pos/length)."""
    dp = data_axes(mesh)
    dp = dp[0] if len(dp) == 1 else dp

    def spec(x):
        if len(x.shape) >= 2:
            return P(None, dp, *([None] * (len(x.shape) - 2)))
        return P()
    return jax.tree_util.tree_map(spec, cache_tree)


def shard_params(params: Any, specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)
