"""Pluggable client execution: how one round's sampled clients are trained.

The FL loop (``repro.core.fl_loop``) is algorithm-agnostic; this module makes
it *execution*-agnostic too.  A ``ClientExecutor`` consumes the round inputs
(global params, broadcast payload, per-client states and data shards) and
produces the round outputs (uploads, weights, local losses, new states) —
how the clients actually run is its business:

    SequentialExecutor   one jitted lax.scan per client, Python loop over
                         clients — the reference semantics
    VmapExecutor         stack the sampled clients' full shards and vmap
                         the SAME scan (or run the client-batched body) so
                         one jitted XLA call trains every client in
                         parallel, each step gathering its batch on the
                         device
    ShardMapExecutor     the multi-device path: the cohort is sharded over a
                         ``("clients",)`` device mesh with shard_map, client
                         shards live device-resident across rounds, and
                         cohorts that do not divide the device count are
                         padded with fully masked phantom clients (never a
                         silent fallback; ``strict=True`` raises if the mesh
                         route cannot run at all, i.e. on a single device)
    AsyncExecutor        buffered-asynchronous rounds over a simulated
                         heterogeneous system (``repro.core.systemsim``):
                         staleness-aware aggregation driven by the fl_loop
                         async path, ready-cohort training delegated to one
                         of the executors above (see the class docstring)

All three consume identical batch picks (one shared host-RNG draw, same
order as the historical per-client iterator), so sequential and vmap
outputs agree to float-associativity (~1e-6 on the paper's small models).
The sequential route gathers each batch on the host; the batched routes
ship each sampled client's full shard once (flattened to ``(N, D)`` rows
on the vmap route, lane-dense in the chip's memory) with the ``(S, B)``
picks, and the round body gathers one step's ``(K, B, ...)`` batch at a
time: no ``(K, S, B, ...)`` batch stack exists on the host or the
device.

Masking rules for ragged clients (see ``repro.core.client``):
  * every batch within a client has a uniform size ``min(B, n_k)``; across
    clients batches are zero-padded to the cohort max with a per-example
    mask that zero-weights pads inside the loss — exact, not approximate;
  * clients with fewer steps than the cohort max get whole padded steps
    masked out as identities on (params, opt_state).

The ``precompute_aux`` stage
----------------------------
KD-family algorithms distill from teachers that are FROZEN for the whole
round (FedGKD Eq. 4-5, FedDistill's label table), so their per-example
teacher tensors are round constants.  Executors therefore invoke
``Algorithm.precompute_aux(model, payload, x, y, mask)`` ONCE per round on
each client's full shard — a single jitted, inference-only batched forward,
``(K, N_max, ...) -> (K, N_max, C)`` on the stacked path, run as a
``lax.map`` over chunks of ``PRECOMPUTE_CHUNK`` rows so that its
activations stay bounded — and gather the per-batch rows through the
batch picks before the training scan.  The gathered pytree reaches ``loss_fn`` as ``aux``; the
teacher's parameters never enter the differentiated (vmapped) closure.

Contract for ``precompute_aux`` implementations:
  * PURE pytree-in/pytree-out and vmappable over a stacked client axis —
    no Python-side branching on data values;
  * FIXED output pytree structure for a given algorithm: the choice of
    "aux vs no aux" is made per-RoundContext, never per-client or
    per-round, so compiled executables are reused across rounds;
  * inference-only: executors call it outside autodiff and treat the
    result as a constant of the round (accumulate in fp32 — the result
    feeds a loss whose gradients must match the inline recomputation);
  * ``mask`` is the per-example validity vector of the padded shard;
    rows with ``mask == 0`` may contain arbitrary values — consumers see
    them only through batch gathers that the example mask zero-weights;
  * returning ``None`` (the base-class default) disables the stage.

Cross-round caching: when the aux decomposes into independently versioned
parts (``Algorithm.precompute_parts`` — FedGKD-VOTE's M buffered teachers,
of which a round replaces exactly one), the batched executors cache each
part's per-example output under ``(client_id, version_key)`` in
``RoundContext.aux_cache`` and recompute only parts with unseen keys, so
steady-state teacher inference is ~1 shard forward per round instead of M.
Requires the caller to pass stable ``client_ids`` to ``run_round``; cached
values must be bit-reproducible from (part payload, shard) alone.

The client-batched conv route
-----------------------------
On the paper's CV backbones, vmapping ``local_update`` over clients turns
every convolution into a batched-WEIGHT convolution that XLA lowers poorly
(the long-standing ROADMAP item).  Models that declare
``ModelBundle.client_batched`` consume client-STACKED params natively —
``models/resnet.py`` detects 5-D conv weights and routes through the fused
``kernels.grouped_conv.client_batched_conv`` (one feature-grouped conv with
a custom VJP) — so for algorithms that provide ``Algorithm.batched_loss_fn``
the batched executors swap the vmapped round body for
``client_lib.make_batched_local_update``: the global params broadcast to a
``(K, ...)`` stack, one fused ``value_and_grad`` of the summed per-client
losses trains the whole cohort (client params are disjoint, so the sum's
gradient IS the per-client gradients), and short rounds run as an unrolled
step loop (``lax.scan`` over resnet-sized bodies is ~19x slower on CPU).
``RoundContext(client_batched=False)`` forces the historical vmapped body —
the ``benchmarks/executor_bench.py --conv`` naive baseline — and
``ctx.telemetry["round_body"]`` records which body ran.  The ShardMap
executor reuses the same body per mesh shard (each shard trains its g
resident clients as one stacked program).

The multi-device path (ShardMapExecutor)
----------------------------------------
``ShardMapExecutor`` maps the cohort onto a 1-D ``("clients",)`` mesh over
every visible device (``repro.launch.mesh.make_clients_mesh``):

  * cohorts whose size K does not divide the device count are padded to
    ``K_pad = ceil(K / n_dev) * n_dev`` with PHANTOM clients whose step and
    example masks are all zero — the same masking machinery that makes
    ragged clients exact makes the phantoms exact identities, and their
    outputs are sliced off before aggregation and metrics;
  * each sampled client's FULL shard is materialized once into a
    device-resident slab pinned to the client's mesh slot
    (``repro.data.pipeline.ClientSlabStore``, keyed by client id) and
    re-used across rounds — per-round host→device traffic drops to the
    cohort's batch-pick indices and masks, with training batches gathered
    from the resident slab ON the owning device inside the sharded round;
  * the ``precompute_aux`` teacher forward and the ``precompute_parts`` /
    ``ModelBuffer`` part-cache run through the same mesh, so teacher logits
    are computed — and their per-version slabs cached — on the device that
    owns the client;
  * which route actually ran is logged and exposed via
    ``RoundContext.telemetry`` (``route``/``n_devices``/``padded_to``/
    ``placement`` counters); ``ShardMapExecutor(strict=True)`` raises
    instead of ever degrading to the single-device vmap computation.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import client as client_lib, tracing
from repro.core.algorithms import Algorithm
from repro.core.modelzoo import ModelBundle
from repro.data.pipeline import ClientData, ClientSlabStore, slab_rows
from repro.optim import Optimizer

_LOG = logging.getLogger("repro.executor")


# ---------------------------------------------------------------------------
# round inputs/outputs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RoundContext:
    """Everything fixed across rounds that an executor needs."""
    algo: Algorithm
    model: ModelBundle
    opt: Optimizer
    lr: float
    batch_size: int
    epochs: int
    max_batches: Optional[int] = None
    precompute: bool = True   # False forces the inline (no-aux) loss path
    # cap on device-resident client shards (ShardMapExecutor; LRU-evicted
    # past the cap).  None = unbounded — right for full participation, but
    # long partial-participation runs on real accelerators should bound it
    placement_max_resident: Optional[int] = None
    # the CLIENT-BATCHED round body (see "The client-batched conv route" in
    # the module docstring): "auto" uses it whenever the model declares
    # ``client_batched`` AND the algorithm provides ``batched_loss_fn``;
    # False forces the historical vmapped body (the benchmarks' naive
    # baseline); True additionally raises if the pair cannot support it
    client_batched: "bool | str" = "auto"
    # -- fixed-slot wave geometry (the async pipelined path) -------------
    # When set, every batched run_round pads its cohort to ``wave_slots``
    # phantom-masked client slots and its batch stacks to
    # ``pad_steps``/``pad_batch`` steps×examples (full shards to
    # ``pad_rows`` rows), so CHURNING wave sizes hit ONE compiled round
    # body instead of retracing per distinct (K, S, B) — see
    # ``AsyncExecutor``.  None (the default) keeps the historical
    # per-wave-maxima shapes; ``run_federated`` sets ``pad_rows`` to the
    # largest client's shard for synchronous rounds on eager data, since
    # the round body takes full shards.  Padding is exact, not
    # approximate: phantom slots/steps are identities through the masking
    # machinery and are sliced off before anything downstream sees them.
    wave_slots: Optional[int] = None
    pad_steps: Optional[int] = None
    pad_batch: Optional[int] = None
    pad_rows: Optional[int] = None
    # deferred-loss mode: run_round returns local losses as on-device
    # scalars instead of forcing a host sync per wave — the async
    # pipelined loop converts them at aggregation, which is the only
    # point allowed to block (``jax.block_until_ready`` semantics)
    deferred: bool = False

    def __post_init__(self):
        loss_fn = self.algo.loss_fn(self.model)
        # scan-based whole-client pass (vmap/shard_map paths)
        self.local_update = client_lib.make_local_update(loss_fn, self.opt)
        # client-batched whole-cohort pass: the model consumes stacked
        # params natively (conv -> kernels.grouped_conv), so the batched
        # executors can skip vmapping the round body entirely
        self.batched_local_update = None
        if self.client_batched in ("auto", True):
            bloss = (self.algo.batched_loss_fn(self.model)
                     if getattr(self.model, "client_batched", False) else None)
            if bloss is not None:
                self.batched_local_update = client_lib.make_batched_local_update(
                    bloss, self.opt)
            elif self.client_batched is True:
                raise ValueError(
                    f"client_batched=True but model "
                    f"{getattr(self.model, 'name', self.model)!r} / algorithm "
                    f"{self.algo.name!r} has no client-batched form "
                    f"(ModelBundle.client_batched + Algorithm.batched_loss_fn)")
        # per-batch step (sequential path: compiles once per batch SHAPE
        # rather than once per (steps, batch) pair like the scan would)
        self.step = client_lib.make_step(loss_fn, self.opt, jit=True)
        # jitted-artifact cache owned by THIS context (executors must not
        # key a shared cache on id(ctx): the id can be reused after gc and
        # serve another algorithm's compiled round function)
        self.jit_cache: dict = {}
        # hooks left at the Algorithm defaults are no-ops — executors skip
        # the (host + dispatch) work of calling them entirely
        cls = type(self.algo)
        self.has_finalize = cls.client_finalize is not Algorithm.client_finalize
        self.has_state_update = (
            cls.update_client_state is not Algorithm.update_client_state)
        self.has_precompute = (
            self.precompute
            and cls.precompute_aux is not Algorithm.precompute_aux)
        # cross-round cache of per-(client, part-version) precompute outputs
        # (see "The precompute_aux stage" in the module docstring)
        self.aux_cache: dict = {}
        # device-resident per-client shard slabs (ShardMapExecutor) — owned
        # by the context so placement survives across rounds with the jit
        # artifacts it feeds
        self.placement = ClientSlabStore(self.placement_max_resident)
        # per-round observability: which route ran, mesh/padding geometry,
        # placement counters, parts recomputed — written by executors, read
        # by fl_loop logging and the regression tests
        self.telemetry: dict = {}
        # distinct round-body input shape signatures seen so far:
        # ``telemetry["compile_count"] == len(round_shapes)`` counts the
        # round body's signatures (the fixed-slot acceptance criterion), not
        # compiles — it misses the precompute, gather and eval programs and
        # cannot tell a compile from a persistent-cache load.  The tally of
        # every jitted function's traces and compiles is
        # ``repro.core.tracing.Recorder.compiles_by_function``.
        self.round_shapes: set = set()

    def note_round_shape(self, sig: tuple) -> None:
        self.round_shapes.add(sig)
        self.telemetry["compile_count"] = len(self.round_shapes)


@dataclasses.dataclass
class RoundResult:
    """Stacked-back-to-lists round outputs; shapes match the historical
    sequential loop so server_update / privacy / History are untouched."""
    uploads: list[dict]
    weights: list[float]
    local_losses: list[float]
    client_states: list[Any]


@runtime_checkable
class ClientExecutor(Protocol):
    name: str

    def run_round(self, ctx: RoundContext, global_params: Any, payload: Any,
                  client_states: list[Any], client_data: list[ClientData],
                  rng: np.random.Generator,
                  client_ids: Optional[list[int]] = None,
                  picks: Optional[list[np.ndarray]] = None) -> RoundResult:
        """``client_ids`` (stable per-client identifiers, aligned with
        ``client_data``) unlock the cross-round teacher-logit cache for
        algorithms that expose ``precompute_parts``; ``None`` disables
        caching but changes nothing else.  ``picks`` supplies pre-drawn
        batch indices (one ``materialize_picks`` array per client, same
        order as ``client_data``) so a caller that must keep ``rng`` in
        lockstep across processes (multi-host placement) can draw for the
        FULL cohort itself; ``None`` keeps the historical in-executor
        draws."""
        ...


# ---------------------------------------------------------------------------
# batch materialization (shared by all executors)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MaterializedClient:
    xs: np.ndarray      # (S_k, bs_k, ...)
    ys: np.ndarray      # (S_k, bs_k)
    n: int              # true example count (aggregation weight)
    picks: np.ndarray   # (S_k, bs_k) int32 — shard-row index of each example


def materialize_picks(rng: np.random.Generator, data: ClientData,
                      batch_size: int, epochs: int,
                      max_batches: Optional[int] = None) -> np.ndarray:
    """Draw the client's epoch batch INDICES up front: (S_k, bs_k) int32.

    Consumes ``rng`` exactly like the historical lazy ``batch_iterator``
    (one permutation per *started* epoch, partial batches wrap-padded), so
    a given seed yields the same batch sequence under every executor —
    including the shard_map path, which ships only these indices to the
    device and gathers the rows from the resident shard slab there.
    """
    n = data.n
    bs = min(batch_size, n)
    picks: list[np.ndarray] = []
    for _ in range(epochs):
        order = rng.permutation(n)
        for i in range(0, n, bs):
            idx = order[i:i + bs]
            if len(idx) < bs:               # wrap the final partial batch
                idx = np.concatenate([idx, order[: bs - len(idx)]])
            picks.append(idx)
            if max_batches is not None and len(picks) >= max_batches:
                break
        if max_batches is not None and len(picks) >= max_batches:
            break
    return np.stack(picks).astype(np.int32)  # (S_k, bs_k)


def materialize_client(rng: np.random.Generator, data: ClientData,
                       batch_size: int, epochs: int,
                       max_batches: Optional[int] = None) -> MaterializedClient:
    """``materialize_picks`` plus the host-side row gather (the sequential
    executor feeds the gathered batches straight to the device)."""
    sel = materialize_picks(rng, data, batch_size, epochs, max_batches)
    return MaterializedClient(data.x[sel], data.y[sel], data.n, sel)


def client_from_picks(data: ClientData, sel: np.ndarray) -> MaterializedClient:
    """``materialize_client`` with the indices already drawn — the
    multi-host round pre-draws picks for the whole cohort (rng lockstep)
    and hands each executor only its owned slice."""
    sel = np.asarray(sel, np.int32)
    return MaterializedClient(data.x[sel], data.y[sel], data.n, sel)


def _pad_and_stack(picks: list[np.ndarray], full: tuple,
                   k_pad: Optional[int] = None, s_pad: Optional[int] = None,
                   b_pad: Optional[int] = None):
    """The batched round body's inputs: the full shards ``(fx, fy)`` of
    ``full`` (from ``_pad_full_data``), example mask (K, S, B), pick
    indices (K, S, B) and step mask (K, S) on the device.  Padded picks
    point at row 0 — harmless, the example mask zero-weights whatever they
    gather.  ``k_pad``/``s_pad``/``b_pad`` as in ``_pad_and_stack_picks``
    (the fixed-slot wave geometry)."""
    k = max(len(picks), k_pad or 0)
    out, ex_mask, step_mask = _pad_and_stack_picks(picks, k, s_pad, b_pad)
    return (full[0], full[1], jnp.asarray(ex_mask), jnp.asarray(out),
            jnp.asarray(step_mask))


def _pad_and_stack_picks(picks: list[np.ndarray], k_pad: int,
                         s_pad: Optional[int] = None,
                         b_pad: Optional[int] = None):
    """Stack per-client pick indices to (k_pad, S, B) + example mask
    (k_pad, S, B) + step mask (k_pad, S) — the shard_map path's entire
    per-round host→device payload.  Rows beyond ``len(picks)`` are phantom
    clients: all-zero masks make their every step an identity.
    ``s_pad``/``b_pad`` raise S/B to fixed targets (fixed-slot waves)."""
    S = max(max(p.shape[0] for p in picks), s_pad or 0)
    B = max(max(p.shape[1] for p in picks), b_pad or 0)
    out = np.zeros((k_pad, S, B), np.int32)
    ex_mask = np.zeros((k_pad, S, B), np.float32)
    step_mask = np.zeros((k_pad, S), bool)
    for i, p in enumerate(picks):
        s, b = p.shape
        out[i, :s, :b] = p
        ex_mask[i, :s, :b] = 1.0
        step_mask[i, :s] = True
    return out, ex_mask, step_mask


def _pad_clients_axis(tree: Any, k_pad: int) -> Any:
    """Zero-pad every leaf's leading (clients) axis to ``k_pad`` (phantom
    clients' states; their updates are masked out and sliced off)."""
    def pad(leaf):
        k = leaf.shape[0]
        if k == k_pad:
            return leaf
        return jnp.concatenate(
            [leaf, jnp.zeros((k_pad - k,) + leaf.shape[1:], leaf.dtype)])
    return jax.tree_util.tree_map(pad, tree)


# Rows of each client's shard that one pass of the teacher precompute
# takes: the (K, PRECOMPUTE_CHUNK) chunk's activations are what the stage
# holds at once (ResNet-50 at 64x64: about 1.1 MB an image, so 4 clients x
# 256 rows ~ 1.1 GB of the chip's 16; PERF.md).  Full shards are padded to
# a multiple of it.
PRECOMPUTE_CHUNK = 256


def _chunk_rows(rows: int) -> int:
    """Rows a precompute chunk takes of a ``rows``-row shard."""
    return max(1, min(PRECOMPUTE_CHUNK, rows))


def _full_rows(n: int) -> int:
    """``n`` rounded up to a whole number of precompute chunks."""
    c = _chunk_rows(n)
    return -(-n // c) * c


def _pad_full_data(client_data: list[ClientData], cache: Optional[dict] = None,
                   cohort_key=None, k_pad: Optional[int] = None,
                   n_pad: Optional[int] = None):
    """Stack each client's FULL shard to (K, N_max, D) rows, each example
    flattened to D values (lane-dense on the chip, whose layouts pad a
    3-channel image's minor axis to 128 lanes), + labels (K, N_max) + mask
    for the round body, the ``precompute_aux`` and the ``client_finalize``
    hooks.  N_max is the cohort's largest shard, or ``n_pad`` if larger,
    rounded up to a whole number of precompute chunks.

    Shards are immutable across rounds, so with ``cache``/``cohort_key``
    (the sampled client-id tuple) a repeated cohort skips the host padding
    and the upload entirely.  The cache holds ONE entry: only a cohort
    repeated back-to-back (fixed-cohort loops, benchmarks) ever hits —
    under random partial participation every round keys differently, and
    retaining misses would pin (K, N_max, ...) device stacks for nothing.
    ``input_upload_bytes`` counts the image bytes a miss uploads.

    ``k_pad`` raises the client dimension to a fixed target (fixed-slot
    waves); phantom rows carry zero values behind a zero mask.
    """
    if cache is not None and cohort_key is not None:
        hit = cache.get(cohort_key)
        if hit is not None:
            return hit
    n_max = _full_rows(max(max(d.n for d in client_data), n_pad or 0))
    k = max(len(client_data), k_pad or 0)
    d0 = client_data[0].x
    xs = np.zeros((k, n_max, int(np.prod(d0.shape[1:]))), d0.dtype)
    ys = np.zeros((k, n_max), client_data[0].y.dtype)
    mask = np.zeros((k, n_max), np.float32)
    for i, d in enumerate(client_data):
        xs[i, :d.n] = d.x.reshape(d.n, -1)
        ys[i, :d.n] = d.y
        mask[i, :d.n] = 1.0
    tracing.count("input_upload_bytes", xs.nbytes)
    out = jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask)
    if cache is not None and cohort_key is not None:
        cache.clear()                   # single-entry: no device-array pile
        cache[cohort_key] = out
    return out


def _chunked_precompute(ctx: "RoundContext", payload, fx, fy, fmask,
                        x_shape: tuple):
    """``precompute_aux`` over every client's full shard, ``fx`` (K, R,
    ...) with examples of shape ``x_shape``: a ``lax.map`` over chunks of
    ``_chunk_rows(R)`` rows (R padded to a whole number of them inside), so
    the teacher's activations are a chunk's, not the shard's.  Leaves come
    back (K, R, ...)."""
    k, rows = fx.shape[:2]
    c, r = _chunk_rows(rows), _full_rows(rows)
    if r != rows:
        pad = lambda a: jnp.pad(a, ((0, 0), (0, r - rows))
                                + ((0, 0),) * (a.ndim - 2))
        fx, fy, fmask = pad(fx), pad(fy), pad(fmask)

    def chunk(i):
        part = lambda a: jax.lax.dynamic_slice_in_dim(a, i * c, c, axis=1)
        x = part(fx).reshape((k, c) + tuple(x_shape))
        return jax.vmap(lambda x, y, m: ctx.algo.precompute_aux(
            ctx.model, payload, x, y, m))(x, part(fy), part(fmask))

    out = jax.lax.map(chunk, jnp.arange(r // c))    # leaves (r/c, K, c, ..)
    return jax.tree_util.tree_map(
        lambda l: jnp.moveaxis(l, 0, 1).reshape((k, r) + l.shape[3:])
        [:, :rows], out)


def tree_stack(trees: list[Any]) -> Any:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def tree_unstack(tree: Any, k: int) -> list[Any]:
    return [jax.tree_util.tree_map(lambda l: l[i], tree) for i in range(k)]


@functools.partial(jax.jit, static_argnums=1)
def _tree_unstack_jit(tree: Any, k: int) -> list[Any]:
    """tree_unstack as ONE dispatch (eager per-leaf slicing costs ~K·L tiny
    device ops per round, which dominates small-model rounds)."""
    return tree_unstack(tree, k)


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------

class SequentialExecutor:
    """Reference implementation: clients one at a time, one jitted step per
    batch (the historical loop — no padding, no masks)."""

    name = "sequential"

    def _precompute_fn(self, ctx: RoundContext) -> Callable:
        fn = ctx.jit_cache.get("precompute_seq")
        if fn is None:
            def stage(payload, x, y, mask, picks):
                aux = ctx.algo.precompute_aux(ctx.model, payload, x, y, mask)
                # gather every step's batch rows in one dispatch: (S, B, ...)
                return jax.tree_util.tree_map(lambda l: l[picks], aux)

            fn = jax.jit(stage)
            ctx.jit_cache["precompute_seq"] = fn
        return fn

    def run_round(self, ctx, global_params, payload, client_states,
                  client_data, rng, client_ids=None,
                  picks=None) -> RoundResult:
        ctx.telemetry["route"] = "sequential"
        uploads, weights, losses, new_states = [], [], [], []
        for ci, (state, cdata) in enumerate(zip(client_states, client_data)):
            mat = (client_from_picks(cdata, picks[ci])
                   if picks is not None else
                   materialize_client(rng, cdata, ctx.batch_size, ctx.epochs,
                                      ctx.max_batches))
            if ctx.has_precompute:
                # one jitted (precompute + all-steps gather) dispatch, then
                # cheap per-step numpy views — never per-step device slicing
                gathered = self._precompute_fn(ctx)(
                    payload, jnp.asarray(cdata.x), jnp.asarray(cdata.y),
                    jnp.ones((cdata.n,), jnp.float32), jnp.asarray(mat.picks))
                aux_steps = jax.tree_util.tree_map(np.asarray, gathered)
            params, opt_state = global_params, ctx.opt.init(global_params)
            step_losses = []
            for s in range(mat.xs.shape[0]):
                aux_b = (jax.tree_util.tree_map(lambda l: l[s], aux_steps)
                         if ctx.has_precompute else ())
                params, opt_state, loss, _ = ctx.step(
                    params, opt_state, payload, state,
                    jnp.asarray(mat.xs[s]), jnp.asarray(mat.ys[s]), None,
                    aux_b, ctx.lr)
                step_losses.append(float(loss))
            extras = {}
            if ctx.has_finalize:
                extras = ctx.algo.client_finalize(
                    ctx.model, params, jnp.asarray(cdata.x),
                    jnp.asarray(cdata.y), jnp.ones((cdata.n,), jnp.float32),
                    payload)
            new_states.append(
                ctx.algo.update_client_state(state, params, payload)
                if ctx.has_state_update else state)
            uploads.append({"params": params, **extras})
            weights.append(float(mat.n))
            losses.append(float(np.mean(step_losses)) if step_losses else 0.0)
        return RoundResult(uploads, weights, losses, new_states)


class VmapExecutor:
    """One jitted call per round: vmap the per-client scan over a stacked
    client axis.  Wall-clock stops scaling linearly with participation."""

    name = "vmap"

    # -- cached jitted stages (cache lives on ctx, see RoundContext) -----
    def _round_fn(self, ctx: RoundContext) -> Callable:
        fn = ctx.jit_cache.get("round")
        if fn is None:
            # no buffer donation: the full shards are reused by the next
            # round of the same cohort, and the masks match no output
            if ctx.batched_local_update is not None:
                # client-batched body: one fused cohort program (stacked
                # params through the model, grouped-conv kernels) instead
                # of vmapping the per-client scan — same signature
                fn = jax.jit(ctx.batched_local_update,
                             static_argnames="x_shape")
            else:
                one = ctx.local_update

                def local_update(global_params, payload, states, fx, fy,
                                 picks, ex_mask, aux, step_mask, lr, *,
                                 x_shape=None):
                    return jax.vmap(
                        functools.partial(one, x_shape=x_shape),
                        in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0, None))(
                        global_params, payload, states, fx, fy, picks,
                        ex_mask, aux, step_mask, lr)

                fn = jax.jit(local_update, static_argnames="x_shape")
            ctx.jit_cache["round"] = fn
        return fn

    def _precompute_fn(self, ctx: RoundContext) -> Callable:
        """``precompute(payload, fx, fy, fmask, x_shape=None) -> aux``:
        the teacher precompute over every client's full shard, one jitted
        program (``stage``, also reachable as ``precompute.program``).
        ``fx`` is (K, N, D) flattened rows of examples shaped ``x_shape``,
        or (K, N, ...) whole examples when ``x_shape`` is None.  Rows are
        padded as ``_pad_full_data`` pads them, so shards that it would
        stack to the same rows share one program; aux leaves come back
        (K, rows, ...)."""
        fn = ctx.jit_cache.get("precompute")
        if fn is None:
            def stage(payload, fx, fy, fmask, x_shape):
                # one inference-only batched forward over every client's
                # full shard, in chunks of rows: (K, N_max, D) ->
                # per-example aux leaves
                return _chunked_precompute(ctx, payload, fx, fy, fmask,
                                           x_shape)

            staged = jax.jit(stage, static_argnames="x_shape")

            def precompute(payload, fx, fy, fmask, x_shape=None):
                if x_shape is None:
                    x_shape = fx.shape[2:]
                    fx = fx.reshape(fx.shape[:2] + (-1,))
                n = fx.shape[1]
                rows = _full_rows(max(n, ctx.pad_rows or 0))
                if rows != n:
                    grow = lambda a: jnp.pad(a, ((0, 0), (0, rows - n))
                                             + ((0, 0),) * (a.ndim - 2))
                    fx, fy, fmask = grow(fx), grow(fy), grow(fmask)
                return staged(payload, fx, fy, fmask,
                              x_shape=tuple(x_shape))

            precompute.program = staged
            fn = ctx.jit_cache["precompute"] = precompute
        return fn

    def _gather_fn(self, ctx: RoundContext) -> Callable:
        fn = ctx.jit_cache.get("gather")
        if fn is None:
            # per-batch rows: leaves (K, N_max, ...) -> (K, S, B, ...)
            def precompute_gather(aux, picks):
                return jax.tree_util.tree_map(lambda l: l[picks], aux)

            fn = jax.jit(jax.vmap(precompute_gather))
            ctx.jit_cache["gather"] = fn
        return fn

    def _finalize_fn(self, ctx: RoundContext) -> Callable:
        fn = ctx.jit_cache.get("finalize")
        if fn is None:
            def one(params, x, y, mask, payload):
                return ctx.algo.client_finalize(ctx.model, params, x, y,
                                                mask, payload)

            fn = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, 0, None)))
            ctx.jit_cache["finalize"] = fn
        return fn

    def _state_fn(self, ctx: RoundContext) -> Callable:
        fn = ctx.jit_cache.get("state")
        if fn is None:
            def one(state, params, payload):
                return ctx.algo.update_client_state(state, params, payload)

            fn = jax.jit(jax.vmap(one, in_axes=(0, 0, None)))
            ctx.jit_cache["state"] = fn
        return fn


    def _incremental_aux(self, ctx: RoundContext, payload, parts_spec,
                         client_ids, client_data, full):
        """Cross-round cached precompute: recompute only the parts whose
        version key is new for a sampled client (steady state: ONE teacher
        forward over the stacked cohort per round instead of M), then
        combine.  Missing parts are computed on the stacked (K, N_max)
        shard — one dispatch per missing version, never per client."""
        keys, get_part = parts_spec
        cohort = tuple(client_ids)
        part_fn = ctx.jit_cache.get("part")
        if part_fn is None:
            part_fn = jax.jit(jax.vmap(
                lambda pp, x: ctx.algo.precompute_part(ctx.model, pp, x),
                in_axes=(None, 0)))
            ctx.jit_cache["part"] = part_fn
        fx = full[0]
        for cid in client_ids:
            ctx.aux_cache.setdefault(cid, {})

        stacked_by_key: dict = {}       # freshly computed parts, deduped

        def ensure_stacked(m, key):
            if key not in stacked_by_key:
                stacked_by_key[key] = part_fn(get_part(m), fx)  # (K, N_max, .)
                ctx.telemetry["parts_computed"] = (
                    ctx.telemetry.get("parts_computed", 0) + 1)
            return stacked_by_key[key]

        # fill the per-client numpy cache for any (client, version) misses
        for m, key in enumerate(keys):
            if any(key not in ctx.aux_cache[cid] for cid in client_ids):
                arr = np.asarray(ensure_stacked(m, key))
                for i, (cid, d) in enumerate(zip(client_ids, client_data)):
                    if key not in ctx.aux_cache[cid]:
                        ctx.aux_cache[cid][key] = arr[i, :d.n]

        # per-VERSION device slabs (K, N_max, ...): version keys ROTATE
        # positions every round, so the cache must be keyed by version, not
        # position — a repeated cohort then re-stacks M resident slabs and
        # uploads nothing but the one freshly computed part
        dev = ctx.jit_cache.get("parts_dev")
        if dev is None or dev["cohort"] != cohort:
            dev = {"cohort": cohort, "slabs": {}}
            ctx.jit_cache["parts_dev"] = dev
        slabs = dev["slabs"]
        # slab geometry comes from the (possibly slot-padded) full stack,
        # not the raw cohort: phantom rows stay zero behind the mask
        k = int(fx.shape[0])
        n_max = int(fx.shape[1])
        tail = ctx.aux_cache[client_ids[0]][keys[0]].shape[1:]
        for m, key in enumerate(keys):
            if key in slabs:
                continue
            if key in stacked_by_key:       # freshly computed, already (K,N)
                slabs[key] = stacked_by_key[key]
            else:                           # host assembly of ONE part only
                buf = np.zeros((k, n_max) + tail, np.float32)
                for i, (cid, d) in enumerate(zip(client_ids, client_data)):
                    buf[i, :d.n] = ctx.aux_cache[cid][key]
                slabs[key] = jnp.asarray(buf)
        parts = jnp.stack([slabs[key] for key in keys])   # (P, K, N_max, ..)
        # evict versions that rotated out of the part key set
        keyset = set(keys)
        dev["slabs"] = {kk: v for kk, v in slabs.items() if kk in keyset}
        for cid in client_ids:
            ctx.aux_cache[cid] = {kk: v for kk, v in ctx.aux_cache[cid].items()
                                  if kk in keyset}
        combine_fn = ctx.jit_cache.get("combine")
        if combine_fn is None:
            combine_fn = jax.jit(jax.vmap(
                lambda pl, pr, x, y, msk: ctx.algo.precompute_combine(
                    pl, pr, x, y, msk),
                in_axes=(None, 1, 0, 0, 0)))
            ctx.jit_cache["combine"] = combine_fn
        return combine_fn(payload, jnp.asarray(parts), *full)

    def run_round(self, ctx, global_params, payload, client_states,
                  client_data, rng, client_ids=None,
                  picks=None) -> RoundResult:
        ctx.telemetry["route"] = "vmap"
        ctx.telemetry["round_body"] = (
            "client_batched" if ctx.batched_local_update is not None
            else "vmap")
        k = len(client_data)
        # fixed-slot waves: pad the cohort axis to ``wave_slots`` phantom
        # clients (and rows/steps/batch to the population-wide targets) so
        # every wave, whatever its size, runs the SAME compiled body
        k_pad = max(k, ctx.wave_slots) if ctx.wave_slots else k
        x_shape = tuple(client_data[0].x.shape[1:])
        aux_full = None
        with tracing.span("fl.exec.full_shards"):
            full = _pad_full_data(
                client_data, cache=ctx.jit_cache.setdefault("full_data", {}),
                cohort_key=(tuple(client_ids)
                            if client_ids is not None else None),
                k_pad=k_pad, n_pad=ctx.pad_rows)
        # the hooks that take whole examples see the shards reshaped
        whole = lambda: ((full[0].reshape(full[0].shape[:2] + x_shape),)
                         + full[1:])
        if ctx.has_precompute:
            # rows the teacher forward runs over, against the real ones
            tracing.count("precompute_rows", full[0].shape[0]
                          * full[0].shape[1])
            tracing.count("precompute_rows_real",
                          sum(d.n for d in client_data))
            parts_spec = (ctx.algo.precompute_parts(payload)
                          if client_ids is not None else None)
            with tracing.span("fl.exec.precompute"):
                if parts_spec is not None:
                    aux_full = self._incremental_aux(
                        ctx, payload, parts_spec, client_ids, client_data,
                        whole())
                else:
                    # dispatch the (async) teacher forward FIRST: it needs
                    # no batch picks, so the device crunches it while the
                    # host draws and pads the round's batch picks below
                    tracing.count("precompute_chunks", full[0].shape[1]
                                  // _chunk_rows(full[0].shape[1]))
                    aux_full = self._precompute_fn(ctx)(payload, *full,
                                                        x_shape=x_shape)

        with tracing.span("fl.exec.picks"):
            picks = (list(picks) if picks is not None else
                     [materialize_picks(rng, d, ctx.batch_size, ctx.epochs,
                                        ctx.max_batches)
                      for d in client_data])
        with tracing.span("fl.exec.stack"):
            fx, fy, ex_mask, picks, step_mask = _pad_and_stack(
                picks, full, k_pad=k_pad, s_pad=ctx.pad_steps,
                b_pad=ctx.pad_batch)
            states_real = tree_stack(client_states)
            states_stacked = _pad_clients_axis(states_real, k_pad)
        ctx.note_round_shape(("round", ctx.telemetry["round_body"])
                             + tuple(fx.shape) + tuple(picks.shape))
        with tracing.span("fl.exec.body"):
            aux = (self._gather_fn(ctx)(aux_full, picks)
                   if ctx.has_precompute else ())
            params_padded, mloss_padded = self._round_fn(ctx)(
                global_params, payload, states_stacked, fx, fy, picks,
                ex_mask, aux, step_mask, ctx.lr, x_shape=x_shape)
        # drop phantom slots before anything downstream sees them
        params_stacked = (jax.tree_util.tree_map(lambda l: l[:k],
                                                 params_padded)
                          if k_pad > k else params_padded)
        mloss = mloss_padded[:k] if k_pad > k else mloss_padded

        if ctx.has_finalize:
            fx, fy, fmask = whole()
            extras_stacked = self._finalize_fn(ctx)(params_stacked, fx[:k],
                                                    fy[:k], fmask[:k],
                                                    payload)
        else:
            extras_stacked = {}
        if ctx.has_state_update:
            new_states_stacked = self._state_fn(ctx)(states_real,
                                                     params_stacked, payload)
        else:
            new_states_stacked = None

        per_client = _tree_unstack_jit(
            (params_stacked, extras_stacked), k)
        uploads = [{"params": p, **e} for p, e in per_client]
        new_states = (_tree_unstack_jit(new_states_stacked, k)
                      if ctx.has_state_update else list(client_states))
        if ctx.deferred:
            losses = mloss
        else:
            with tracing.span("fl.exec.losses"):
                losses = np.asarray(mloss).astype(float).tolist()
            tracing.count("host_syncs")
        return RoundResult(uploads, [float(d.n) for d in client_data],
                           losses, new_states)


class ShardMapExecutor(VmapExecutor):
    """The multi-device executor: cohort sharded over a ``("clients",)``
    mesh, client shards device-resident across rounds.

    See "The multi-device path" in the module docstring.  Cohorts that do
    not divide the device count are padded with fully masked phantom
    clients (no fallback); the only configuration the mesh route cannot
    serve is a single-device host, where it degrades to the vmap
    computation with a logged warning — or raises under ``strict=True``.
    """

    name = "shard_map"

    def __init__(self, strict: bool = False):
        self.strict = strict

    # -- mesh + sharded jitted stages ------------------------------------
    def _mesh(self, ctx: RoundContext, ndev: int):
        key = ("clients_mesh", ndev)
        mesh = ctx.jit_cache.get(key)
        if mesh is None:
            from repro.launch.mesh import (make_clients_mesh,
                                           make_local_clients_mesh)
            mesh = (make_local_clients_mesh(ndev)
                    if jax.process_count() > 1 else make_clients_mesh(ndev))
            ctx.jit_cache[key] = mesh
        return mesh

    def _sharded_round_fn(self, ctx: RoundContext, mesh) -> Callable:
        key = ("smap_round", mesh.devices.size)
        jfn = ctx.jit_cache.get(key)
        if jfn is None:
            from repro.sharding import shard_map_compat

            def per_shard(gp, pl, st, fx, fy, picks, ex_mask, step_mask,
                          aux_full):
                # each step's batch rows are gathered from the resident
                # slab ON the device that owns the client, inside the same
                # round bodies the vmap route runs — the host never ships
                # batch tensors for this path
                aux_rows = jax.tree_util.tree_map(
                    lambda l: jax.vmap(lambda a, p: a[p])(l, picks),
                    aux_full)
                if ctx.batched_local_update is not None:
                    # client-batched body on this shard's g resident
                    # clients: the fused stacked round (grouped-conv
                    # route) — no vmap
                    return ctx.batched_local_update(
                        gp, pl, st, fx, fy, picks, ex_mask, aux_rows,
                        step_mask, ctx.lr)
                return jax.vmap(
                    ctx.local_update,
                    in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0, None))(
                    gp, pl, st, fx, fy, picks, ex_mask, aux_rows,
                    step_mask, ctx.lr)

            fn = shard_map_compat(
                per_shard, mesh,
                in_specs=(P(), P(), P("clients"), P("clients"), P("clients"),
                          P("clients"), P("clients"), P("clients"),
                          P("clients")),
                out_specs=(P("clients"), P("clients")))
            jfn = jax.jit(fn)
            ctx.jit_cache[key] = jfn
        return jfn

    def _sharded_precompute_fn(self, ctx: RoundContext, mesh) -> Callable:
        key = ("smap_pre", mesh.devices.size)
        jfn = ctx.jit_cache.get(key)
        if jfn is None:
            from repro.sharding import shard_map_compat

            def per_shard(pl, fx, fy, fmask):
                return _chunked_precompute(ctx, pl, fx, fy, fmask,
                                           fx.shape[2:])

            fn = shard_map_compat(
                per_shard, mesh,
                in_specs=(P(), P("clients"), P("clients"), P("clients")),
                out_specs=P("clients"))
            jfn = jax.jit(fn)
            ctx.jit_cache[key] = jfn
        return jfn

    def _sharded_part_fn(self, ctx: RoundContext, mesh) -> Callable:
        key = ("smap_part", mesh.devices.size)
        jfn = ctx.jit_cache.get(key)
        if jfn is None:
            from repro.sharding import shard_map_compat

            def per_shard(pp, fx):
                return jax.vmap(
                    lambda x: ctx.algo.precompute_part(ctx.model, pp,
                                                       x))(fx)

            fn = shard_map_compat(per_shard, mesh,
                                  in_specs=(P(), P("clients")),
                                  out_specs=P("clients"))
            jfn = jax.jit(fn)
            ctx.jit_cache[key] = jfn
        return jfn

    def _sharded_combine_fn(self, ctx: RoundContext, mesh,
                            n_parts: int) -> Callable:
        key = ("smap_combine", mesh.devices.size, n_parts)
        jfn = ctx.jit_cache.get(key)
        if jfn is None:
            from repro.sharding import shard_map_compat

            def per_shard(pl, parts, fx, fy, fmask):
                stacked = jnp.stack(parts)          # (P, g, rows, ...)
                return jax.vmap(
                    lambda pr, x, y, m: ctx.algo.precompute_combine(
                        pl, pr, x, y, m),
                    in_axes=(1, 0, 0, 0))(stacked, fx, fy, fmask)

            fn = shard_map_compat(
                per_shard, mesh,
                in_specs=(P(), P("clients"), P("clients"), P("clients"),
                          P("clients")),
                out_specs=P("clients"))
            jfn = jax.jit(fn)
            ctx.jit_cache[key] = jfn
        return jfn

    # -- device-resident cohort assembly ---------------------------------
    def _resident_cohort(self, ctx: RoundContext, mesh,
                         client_data: list[ClientData],
                         client_ids: Optional[list[int]], k_pad: int,
                         rows: Optional[int] = None):
        """(k_pad, rows, ...) x/y/mask stacks sharded ``P("clients")``,
        assembled from the per-client resident slabs in ``ctx.placement``.

        Assembly is pure device work (pad + stack of resident arrays);
        the host uploads a shard only the first time a client is seen.
        A back-to-back repeated cohort skips even the device-side
        restack via a single-entry cache (mirrors ``_pad_full_data``)."""
        devices = list(mesh.devices.reshape(-1))
        ndev = len(devices)
        g = k_pad // ndev
        if rows is None:
            rows = max(slab_rows(d.n) for d in client_data)
        cohort_key = (tuple(client_ids), rows, ndev) \
            if client_ids is not None else None
        cache = ctx.jit_cache.setdefault("slab_stack", {})
        if cohort_key is not None and cache.get("key") == cohort_key:
            return cache["value"]

        entries: list[Optional[dict]] = []
        for i, d in enumerate(client_data):
            cid = client_ids[i] if client_ids is not None else None
            uploads = ctx.placement.host_transfers
            entries.append(ctx.placement.get(cid, d, devices[i // g]))
            if ctx.placement.host_transfers > uploads:
                tracing.count("input_upload_bytes", entries[-1]["x"].nbytes)
        feat = client_data[0].x.shape[1:]
        x_dtype = client_data[0].x.dtype
        pad_width = ((0, 0),) * len(feat)
        xs_shards, ys_shards = [], []
        for didx, device in enumerate(devices):
            members = entries[didx * g:(didx + 1) * g]
            xs, ys = [], []
            for e in members:
                short = rows - e["rows"]
                ex, ey = e["x"], e["y"]
                if short:
                    ex = jnp.pad(ex, ((0, short),) + pad_width)
                    ey = jnp.pad(ey, ((0, short),))
                xs.append(ex)
                ys.append(ey)
            for _ in range(g - len(members)):           # phantom clients
                xs.append(jnp.zeros((rows,) + feat, x_dtype))
                ys.append(jnp.zeros((rows,), jnp.int32))
            xs_shards.append(jax.device_put(jnp.stack(xs), device))
            ys_shards.append(jax.device_put(jnp.stack(ys), device))
        sharding = NamedSharding(mesh, P("clients"))
        fx = jax.make_array_from_single_device_arrays(
            (k_pad, rows) + feat, sharding, xs_shards)
        fy = jax.make_array_from_single_device_arrays(
            (k_pad, rows), sharding, ys_shards)
        mask = np.zeros((k_pad, rows), np.float32)
        for i, d in enumerate(client_data):
            mask[i, :d.n] = 1.0
        # process-local -> global assembly: single-process this is a
        # device_put; in a multi-process topology every host contributes
        # the mask rows its devices own (same shim for both)
        from repro.sharding import make_array_from_process_local_data_compat
        fmask = make_array_from_process_local_data_compat(sharding, mask)
        out = (fx, fy, fmask)
        if cohort_key is not None:
            cache.clear()
            cache["key"] = cohort_key
            cache["value"] = out
        return out

    def _stack_to_mesh(self, mesh, pieces: list, rows: int, k_pad: int,
                       dtype):
        """Assemble per-client device arrays ``(rows_i, ...)`` into one
        ``(k_pad, rows, ...)`` stack sharded ``P("clients")`` — pad/trim
        each piece to ``rows`` on its slot device, phantom slots zero.
        Device work only; nothing round-trips through the host."""
        devices = list(mesh.devices.reshape(-1))
        g = k_pad // len(devices)
        tail = pieces[0].shape[1:]
        pad_width = ((0, 0),) * len(tail)
        shards = []
        for didx, device in enumerate(devices):
            members = pieces[didx * g:(didx + 1) * g]
            arrs = []
            for p in members:
                p = jax.device_put(p, device)
                if p.shape[0] < rows:
                    p = jnp.pad(p, ((0, rows - p.shape[0]),) + pad_width)
                elif p.shape[0] > rows:
                    p = p[:rows]
                arrs.append(p)
            for _ in range(g - len(arrs)):
                arrs.append(jnp.zeros((rows,) + tail, dtype))
            shards.append(jax.device_put(jnp.stack(arrs), device))
        return jax.make_array_from_single_device_arrays(
            (k_pad, rows) + tail, NamedSharding(mesh, P("clients")), shards)

    def _incremental_aux_sharded(self, ctx: RoundContext, mesh, payload,
                                 parts_spec, client_ids, client_data, full):
        """The parts cache on the mesh.  Two layers, mirroring the vmap
        path but with everything device-resident:

          * per-(client_id, version) part outputs in ``ctx.aux_cache`` —
            device arrays trimmed to the client's own slab rows, so the
            cache survives cohort churn under partial participation;
          * per-version ``(k_pad, rows, ...)`` slabs sharded
            ``P("clients")`` in ``jit_cache["parts_smap"]``, rebuilt from
            the per-client layer when the cohort (or its slab geometry)
            changes — a reassembly, not a recompute.

        A version is recomputed (ONE sharded teacher forward over the
        whole cohort) only when some sampled client has never seen it —
        the steady state stays one forward per round however the cohort
        rotates."""
        keys, get_part = parts_spec
        fx, fy, fmask = full
        rows = int(fx.shape[1])
        k_pad = int(fx.shape[0])
        cohort = (tuple(client_ids), rows)
        for cid in client_ids:
            ctx.aux_cache.setdefault(cid, {})
        dev = ctx.jit_cache.get("parts_smap")
        if dev is None or dev["cohort"] != cohort:
            dev = {"cohort": cohort, "slabs": {}}
            ctx.jit_cache["parts_smap"] = dev
        slabs = dev["slabs"]
        part_fn = self._sharded_part_fn(ctx, mesh)
        own_rows = [slab_rows(d.n) for d in client_data]
        for m, key in enumerate(keys):
            if key in slabs:
                continue
            if any(key not in ctx.aux_cache[cid] for cid in client_ids):
                out = part_fn(get_part(m), fx)      # sharded (k_pad, R, .)
                ctx.telemetry["parts_computed"] = (
                    ctx.telemetry.get("parts_computed", 0) + 1)
                for i, cid in enumerate(client_ids):
                    if key not in ctx.aux_cache[cid]:
                        ctx.aux_cache[cid][key] = out[i, :own_rows[i]]
                slabs[key] = out
            else:                   # every client resident: reassemble only
                slabs[key] = self._stack_to_mesh(
                    mesh, [ctx.aux_cache[cid][key] for cid in client_ids],
                    rows, k_pad, jnp.float32)
        keyset = set(keys)
        dev["slabs"] = {kk: v for kk, v in slabs.items() if kk in keyset}
        for cid in client_ids:
            ctx.aux_cache[cid] = {kk: v for kk, v in
                                  ctx.aux_cache[cid].items() if kk in keyset}
        combine = self._sharded_combine_fn(ctx, mesh, len(keys))
        parts = tuple(dev["slabs"][key] for key in keys)
        return combine(payload, parts, fx, fy, fmask)

    # -- the round ---------------------------------------------------------
    def run_round(self, ctx, global_params, payload, client_states,
                  client_data, rng, client_ids=None,
                  picks=None) -> RoundResult:
        # a multi-process topology shards each host's owned cohort slice
        # over its LOCAL devices; single-process the two sets are equal
        ndev = (len(jax.local_devices()) if jax.process_count() > 1
                else len(jax.devices()))
        if ndev == 1:
            if self.strict:
                raise RuntimeError(
                    "ShardMapExecutor(strict=True): only one device is "
                    "visible, the clients mesh cannot run.  On a CPU host "
                    "set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "before the first jax import, or drop strict to allow "
                    "the vmap fallback.")
            _LOG.warning(
                "shard_map executor: single visible device — degrading to "
                "the vmap computation (set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N for a real mesh)")
            result = super().run_round(ctx, global_params, payload,
                                       client_states, client_data, rng,
                                       client_ids, picks)
            ctx.telemetry.update(route="vmap-fallback", n_devices=1)
            return result
        return self._run_sharded(ctx, global_params, payload, client_states,
                                 client_data, rng, client_ids, ndev,
                                 picks=picks)

    def _run_sharded(self, ctx, global_params, payload, client_states,
                     client_data, rng, client_ids, ndev,
                     picks=None) -> RoundResult:
        mesh = self._mesh(ctx, ndev)
        k = len(client_data)
        # fixed-slot waves: pad cohorts up to ``wave_slots`` BEFORE the
        # mesh rounding so every wave lands on the same (k_pad, rows, S, B)
        # geometry and the sharded round body never retraces
        k_eff = max(k, ctx.wave_slots) if ctx.wave_slots else k
        g = -(-k_eff // ndev)
        k_pad = g * ndev
        rows = max(slab_rows(d.n) for d in client_data)
        if ctx.pad_rows is not None:
            rows = max(rows, slab_rows(ctx.pad_rows))
        full = self._resident_cohort(ctx, mesh, client_data, client_ids,
                                     k_pad, rows=rows)
        aux_full: Any = ()
        if ctx.has_precompute:
            parts_spec = (ctx.algo.precompute_parts(payload)
                          if client_ids is not None else None)
            if parts_spec is not None:
                aux_full = self._incremental_aux_sharded(
                    ctx, mesh, payload, parts_spec, client_ids, client_data,
                    full)
            else:
                tracing.count("precompute_chunks",
                              _full_rows(rows) // _chunk_rows(rows))
                aux_full = self._sharded_precompute_fn(ctx, mesh)(payload,
                                                                  *full)

        picks_list = (list(picks) if picks is not None else
                      [materialize_picks(rng, d, ctx.batch_size, ctx.epochs,
                                         ctx.max_batches)
                       for d in client_data])
        picks, ex_mask, step_mask = _pad_and_stack_picks(
            picks_list, k_pad, s_pad=ctx.pad_steps, b_pad=ctx.pad_batch)
        sharding = NamedSharding(mesh, P("clients"))
        picks = jax.device_put(picks, sharding)
        ex_mask = jax.device_put(ex_mask, sharding)
        step_mask = jax.device_put(step_mask, sharding)
        states_stacked = tree_stack(client_states)
        states_padded = _pad_clients_axis(states_stacked, k_pad)

        fx, fy, fmask = full
        ctx.note_round_shape(("smap_round", ndev, rows)
                             + tuple(picks.shape))
        params_padded, mloss_padded = self._sharded_round_fn(ctx, mesh)(
            global_params, payload, states_padded, fx, fy, picks, ex_mask,
            step_mask, aux_full)
        # drop the phantom clients before anything downstream sees them
        params_stacked = jax.tree_util.tree_map(lambda l: l[:k],
                                                params_padded)
        mloss = mloss_padded[:k]

        if ctx.has_finalize:
            extras_stacked = self._finalize_fn(ctx)(
                params_stacked, fx[:k], fy[:k], fmask[:k], payload)
        else:
            extras_stacked = {}
        if ctx.has_state_update:
            new_states_stacked = self._state_fn(ctx)(states_stacked,
                                                     params_stacked, payload)
        else:
            new_states_stacked = None

        per_client = _tree_unstack_jit((params_stacked, extras_stacked), k)
        uploads = [{"params": p, **e} for p, e in per_client]
        new_states = (_tree_unstack_jit(new_states_stacked, k)
                      if ctx.has_state_update else list(client_states))
        ctx.telemetry.update(route="shard_map", n_devices=ndev, cohort=k,
                             padded_to=k_pad,
                             round_body=("client_batched"
                                         if ctx.batched_local_update
                                         is not None else "vmap"),
                             placement=ctx.placement.stats())
        _LOG.debug("shard_map round: K=%d padded to %d on %d devices", k,
                   k_pad, ndev)
        return RoundResult(uploads, [float(d.n) for d in client_data],
                           mloss if ctx.deferred
                           else np.asarray(mloss).astype(float).tolist(),
                           new_states)


class AsyncExecutor:
    """Straggler-aware buffered-asynchronous rounds.

    This executor changes the ROUND STRUCTURE, not just how a cohort
    trains: clients run on a simulated heterogeneous system
    (``repro.core.systemsim``), dispatch local updates tagged with the
    global version they started from, and the server aggregates a buffer
    of ``buffer_size`` completions with pluggable staleness weighting
    (``repro.core.server.async_aggregation_weights``).  The sampled
    in-flight concurrency stays at the task's cohort size; every
    aggregation consumes the B earliest completions and refills the fleet
    with B freshly sampled idle clients.

    Because the structure differs, the drive loop lives in
    ``repro.core.fl_loop`` (version counters, async history records); this
    class is the configuration + the READY-COHORT trainer: each dispatch
    wave — the clients starting from the same global version — is trained
    through an ordinary inner executor (``vmap``/``shard_map``/
    ``sequential``), so the jitted round bodies, the teacher-precompute
    pipeline and the device-resident slab placement are all reused
    unchanged.  In the degenerate regime (homogeneous speeds, full buffer
    B == cohort, zero staleness) the async loop reproduces the synchronous
    executors' numbers to < 1e-5 — the equivalence suite pins that down.

    Knobs:
      buffer_size       aggregation buffer B (default: the cohort size —
                        the synchronous-equivalent "full buffer")
      staleness         "constant" | "polynomial" | "fedgkd" (the KD
                        teacher buffer absorbs stale models, see
                        ``Algorithm.absorb_stale``)
      staleness_a       polynomial decay exponent (1+s)^(-a)
      staleness_cutoff  fedgkd scheme: staleness beyond this is dropped
                        from averaging (absorbed only); None = never drop
      profile           ``systemsim.SpeedProfile`` for per-client speeds
      availability      optional ``systemsim.Availability`` duty cycle
      inner             ready-cohort executor spec or instance
      base_step_time    virtual seconds per unit of local work — calibrate
                        with ``systemsim.measure_step_time`` to make
                        ``sim_time`` a wall-clock prediction
      pipelined         True (default) overlaps wave N+1's dispatch — the
                        host-side slab gather / batch materialization and
                        the teacher ``precompute_aux`` — with wave N's
                        on-device training: the inner executor defers its
                        loss sync (``RoundContext.deferred``) and the
                        drive loop refills the fleet BEFORE the eval
                        forces, so ``jax.block_until_ready`` happens only
                        at aggregation.  False restores the historical
                        single-stream order (the throughput benchmark's
                        baseline); values are identical either way.
      wave_slots        "auto" (default) pads every dispatch wave to a
                        fixed slot count — the buffer size — on the
                        batched inners, pinning ONE compiled round body
                        across wave-size churn (``telemetry
                        ["compile_count"]`` proves it); an int forces the
                        slot count, None/"variable" keeps the historical
                        per-wave shapes (which retrace per distinct
                        geometry).  The sequential inner has no stacked
                        shapes to pin and always runs variable.

    Fault tolerance composes from the OUTSIDE, not here: pass
    ``run_federated(faults=systemsim.FaultProfile(...))`` and the async
    drive loop draws per-dispatch crash/timeout/corrupt faults from the
    dedicated fault stream, validates completions at buffer-fill time
    (``server.validate_update``), and re-dispatches failed clients with
    capped backoff on the simulated clock — the same knobs drive the
    synchronous executors, so faults fire identically across routes.
    """

    name = "async"

    def __init__(self, buffer_size: Optional[int] = None,
                 staleness: str = "polynomial", staleness_a: float = 0.5,
                 staleness_cutoff: Optional[float] = None,
                 profile=None, availability=None,
                 inner: "str | ClientExecutor" = "auto",
                 base_step_time: float = 1.0,
                 pipelined: bool = True,
                 wave_slots: "int | str | None" = "auto"):
        from repro.core.server import STALENESS_SCHEMES
        if staleness not in STALENESS_SCHEMES:
            raise ValueError(f"unknown staleness scheme {staleness!r}; "
                             f"available: {STALENESS_SCHEMES}")
        if isinstance(inner, str) and inner == "async":
            raise ValueError("AsyncExecutor cannot nest itself as inner")
        if isinstance(wave_slots, str) and wave_slots not in ("auto",
                                                              "variable"):
            raise ValueError(f"wave_slots must be 'auto', 'variable', an "
                             f"int or None, got {wave_slots!r}")
        if isinstance(wave_slots, int) and wave_slots < 1:
            raise ValueError(f"wave_slots must be >= 1, got {wave_slots}")
        self.buffer_size = buffer_size
        self.staleness = staleness
        self.staleness_a = staleness_a
        self.staleness_cutoff = staleness_cutoff
        self.profile = profile
        self.availability = availability
        self.inner = inner
        self.base_step_time = base_step_time
        self.pipelined = pipelined
        self.wave_slots = wave_slots

    def resolve_inner(self, algo: Algorithm, n_sample: int,
                      model: Optional[ModelBundle] = None) -> ClientExecutor:
        resolved = get_executor(self.inner, algo, n_sample, model)
        if isinstance(resolved, AsyncExecutor):
            raise ValueError("AsyncExecutor cannot nest itself as inner")
        return resolved

    def resolve_wave_slots(self, buffer_size: int,
                           inner: ClientExecutor) -> Optional[int]:
        """The fixed wave slot count for this run, or None for variable
        waves.  "auto" resolves to the aggregation buffer size: refills
        dispatch exactly B clients, redispatches pad 1 → B, and the
        initial ``n_sample`` wave chunks into ceil(n_sample / B) calls of
        the SAME B-slot body (see ``fl_loop._run_async``) — so one shape
        covers every wave.  The sequential inner trains clients one at a
        time (no stacked shapes) and always runs variable."""
        if self.wave_slots in (None, "variable"):
            return None
        if getattr(inner, "name", None) == "sequential":
            return None
        return buffer_size if self.wave_slots == "auto" else self.wave_slots

    def run_round(self, ctx, global_params, payload, client_states,
                  client_data, rng, client_ids=None) -> RoundResult:
        raise NotImplementedError(
            "AsyncExecutor rounds are event-driven, not cohort-at-a-time; "
            "drive it through run_federated(..., executor=\"async\") (the "
            "buffered-aggregation loop lives in repro.core.fl_loop)")


# ---------------------------------------------------------------------------
# registry / resolution
# ---------------------------------------------------------------------------

_EXECUTORS = {
    "sequential": SequentialExecutor,
    "vmap": VmapExecutor,
    "shard_map": ShardMapExecutor,
    "async": AsyncExecutor,
}


def available() -> list[str]:
    return sorted(_EXECUTORS) + ["auto"]


def get_executor(spec: "str | ClientExecutor", algo: Algorithm,
                 n_sample: int,
                 model: Optional[ModelBundle] = None) -> ClientExecutor:
    """Resolve an executor spec.

    ``"auto"`` picks the batched vmap path when the algorithm declares
    ``supports_vmap``, more than one client is sampled per round, AND the
    model batches well — either its ops lower well under stacked-weight
    vmap (``vmap_friendly``: dense models) or it has the client-batched
    route (``client_batched`` models whose algorithm provides
    ``batched_loss_fn``, e.g. the resnet backbones through
    ``kernels.grouped_conv``); otherwise the sequential reference.
    Instances pass through unchanged.
    """
    if not isinstance(spec, str):
        return spec
    if spec == "auto":
        model_ok = (model is None or model.vmap_friendly
                    or (getattr(model, "client_batched", False)
                        and algo.batched_loss_fn(model) is not None))
        batched_ok = (getattr(algo, "supports_vmap", False) and n_sample > 1
                      and model_ok)
        spec = "vmap" if batched_ok else "sequential"
    try:
        return _EXECUTORS[spec]()
    except KeyError:
        raise ValueError(
            f"unknown executor {spec!r}; available: {available()}") from None
