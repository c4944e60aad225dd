"""Server side: weighted aggregation + the FedGKD global-model buffer.

``ModelBuffer`` is the M-deep FIFO of historical global weights (Alg. 1,
line 11).  For FedGKD the server ships only the fused mean (communication =
2× FedAvg, == 1× when M == 1); FedGKD-VOTE ships all M entries.

Update validation (the fault-tolerance gate)
--------------------------------------------
``validate_update`` is the server's admission check on every client
upload when fault handling is on (``run_federated(faults=)``): non-finite
parameters (a diverged or corrupted client) and norm outliers (an update
whose L2 norm exceeds ``FaultPolicy.max_norm_mult`` × the current global's)
are rejected BEFORE they reach aggregation or the FedGKD teacher buffer —
a poisoned historical teacher would distill its damage into every
subsequent local step.  ``FaultPolicy`` also owns the degradation knobs:
``quorum_frac`` (a sync round aggregates once this fraction of the cohort
survives, weights renormalized over survivors) and the capped exponential
retry backoff applied to crashed/rejected clients on the simulated clock.

Staleness-aware aggregation (the async path)
--------------------------------------------
The buffered-asynchronous server (``fl_loop`` with ``executor="async"``)
aggregates a buffer of B client updates, each tagged with the global
version it STARTED from; ``staleness = current_version - start_version``.
``async_aggregation_weights`` combines the FedAvg data weights with a
pluggable per-update staleness multiplier (``staleness_scale``):

    constant      stale updates count like fresh ones
    polynomial    (1 + s)^(-a) — FedAsync-style polynomial decay
    fedgkd        polynomial decay, but updates past ``cutoff`` are
                  DROPPED from parameter averaging (weight 0) and instead
                  absorbed into the KD teacher buffer via the algorithm's
                  ``absorb_stale`` hook — stale knowledge distills rather
                  than drags the global model backwards

Invariants the property suite pins down: scales are non-negative, the
normalized weights sum to 1, and the polynomial scale is monotone
non-increasing in staleness.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import tracing
from repro.core.distillation import ensemble_average

STALENESS_SCHEMES = ("constant", "polynomial", "fedgkd")


@dataclasses.dataclass(frozen=True)
class FaultPolicy:
    """How the server degrades under client failures.

        quorum_frac     a sync round proceeds once this fraction of the
                        sampled cohort has produced a VALID update
                        (weights renormalize over the survivors)
        max_retries     retry attempts per round (sync) / per client
                        (async re-dispatch) before giving up on the
                        failed clients
        backoff_base    first retry waits this many virtual seconds;
        backoff_cap     each further attempt doubles it, capped here
        max_norm_mult   ``validate_update`` rejects an update whose L2
                        norm exceeds this multiple of the global's
    """
    quorum_frac: float = 0.6
    max_retries: int = 3
    backoff_base: float = 1.0
    backoff_cap: float = 30.0
    max_norm_mult: float = 10.0

    def __post_init__(self):
        if not (0.0 < self.quorum_frac <= 1.0):
            raise ValueError(f"quorum_frac in (0, 1], got {self.quorum_frac}")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")

    def backoff(self, attempt: int) -> float:
        """Capped exponential backoff for retry ``attempt`` (1-based)."""
        return min(self.backoff_cap,
                   self.backoff_base * (2.0 ** (attempt - 1)))


def first_nonfinite_path(tree: Any) -> Optional[str]:
    """'/'-joined path of the first leaf containing NaN/Inf, else None.
    Integer/bool leaves are always finite and skipped without transfer."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in flat:
        arr = jnp.asarray(leaf)
        if not jnp.issubdtype(arr.dtype, jnp.inexact):
            continue
        tracing.count("host_syncs")
        if not bool(jnp.all(jnp.isfinite(arr.astype(jnp.float32)))):
            return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
    return None


def _global_norm(tree: Any) -> float:
    leaves = [jnp.asarray(l) for l in jax.tree_util.tree_leaves(tree)]
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    return float(jnp.sqrt(sq))


def validate_update(params: Any, ref_params: Any = None, *,
                    max_norm_mult: float = 10.0) -> tuple[bool, str]:
    """Admission check for one client upload: ``(True, "ok")`` or
    ``(False, reason)`` with reason ``"nonfinite:<leaf path>"`` or
    ``"norm:<ratio>x"``.  The norm gate compares against ``ref_params``
    (the current global) with a floor of 1.0 so a near-zero reference
    cannot reject everything."""
    bad = first_nonfinite_path(params)
    if bad is not None:
        return False, f"nonfinite:{bad}"
    if ref_params is not None and max_norm_mult is not None:
        ref = max(_global_norm(ref_params), 1.0)
        ratio = _global_norm(params) / ref
        if ratio > max_norm_mult:
            return False, f"norm:{ratio:.1f}x"
    return True, "ok"


def weighted_average(params_list: list[Any], weights: list[float]) -> Any:
    """FedAvg aggregation  w ← Σ_k (n_k/n)·w_k  (Alg. 1 line 14)."""
    total = float(sum(weights))
    norm = [w / total for w in weights]

    def agg(*leaves):
        acc = norm[0] * leaves[0].astype(jnp.float32)
        for w, leaf in zip(norm[1:], leaves[1:]):
            acc = acc + w * leaf.astype(jnp.float32)
        return acc.astype(leaves[0].dtype)

    return jax.tree_util.tree_map(agg, *params_list)


def staleness_scale(staleness: float, scheme: str = "polynomial", *,
                    a: float = 0.5, cutoff: "float | None" = None) -> float:
    """Per-update multiplier for an update that started ``staleness``
    versions ago.  Non-negative; ``polynomial`` is monotone non-increasing
    in staleness; ``constant`` is exactly 1.0 (so the async path with zero
    staleness reproduces the synchronous weights bit-for-bit)."""
    if scheme not in STALENESS_SCHEMES:
        raise ValueError(f"unknown staleness scheme {scheme!r}; "
                         f"available: {STALENESS_SCHEMES}")
    s = float(staleness)
    assert s >= 0.0, f"negative staleness {s}"
    if scheme == "constant":
        return 1.0
    if scheme == "fedgkd" and cutoff is not None and s > cutoff:
        return 0.0
    return (1.0 + s) ** (-a)


def async_aggregation_weights(data_weights: Sequence[float],
                              staleness: Sequence[float],
                              scheme: str = "polynomial", *,
                              a: float = 0.5,
                              cutoff: "float | None" = None,
                              normalize: bool = True) -> list[float]:
    """Combine FedAvg data weights with staleness multipliers.

    With ``normalize=True`` the result is a distribution (non-negative,
    sums to 1).  ``normalize=False`` returns the raw products for callers
    that feed ``weighted_average`` (which normalizes internally) — under
    the constant scheme the raw products ARE the synchronous n_k weights.
    If every update scaled to zero (an all-stale buffer past the fedgkd
    cutoff) the data weights are used unscaled: the aggregation must stay
    well-defined, and the absorb path has already captured the knowledge.
    """
    assert len(data_weights) == len(staleness)
    raw = [float(n) * staleness_scale(s, scheme, a=a, cutoff=cutoff)
           for n, s in zip(data_weights, staleness)]
    if sum(raw) <= 0.0:
        raw = [float(n) for n in data_weights]
    if not normalize:
        return raw
    total = sum(raw)
    return [r / total for r in raw]


def _trees_identical(a: Any, b: Any) -> bool:
    """Bitwise pytree equality (structure + every array element)."""
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        return False
    for x, y in zip(la, lb):
        x, y = jnp.asarray(x), jnp.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        tracing.count("host_syncs")
        if not bool(jnp.all(x == y)):
            return False
    return True


class ModelBuffer:
    """FIFO of the latest M global models.

    Every pushed model gets a monotonically increasing version number so
    downstream consumers (the executor teacher-logit cache — see
    ``repro.core.executor``) can tell WHICH buffer entries changed between
    rounds: a push replaces one entry and leaves M−1 identical.

    ``push`` is hardened as the last line of defense for the KD teacher
    ensemble: non-finite candidates raise (a poisoned teacher distills
    its damage into every subsequent local step — the quarantine in the
    fault-handling loop should have filtered them long before here), and
    a candidate bitwise-identical to the current head is a no-op
    returning False — no version bump, so the executor part-caches stay
    warm and a retry/replay can never double-insert the same teacher.
    """

    def __init__(self, size: int):
        assert size >= 1
        self.size = size
        self._buf: collections.deque = collections.deque(maxlen=size)
        self._versions: collections.deque = collections.deque(maxlen=size)
        self._next_version = 0

    def push(self, params: Any) -> bool:
        with tracing.span("fl.server.push"):
            bad = first_nonfinite_path(params)
            if bad is not None:
                raise ValueError(
                    f"ModelBuffer.push: non-finite teacher candidate at "
                    f"leaf {bad!r} — rejected updates must be quarantined "
                    f"before they reach the KD buffer")
            if self._buf and _trees_identical(params, self._buf[-1]):
                return False
        self._buf.append(params)
        self._versions.append(self._next_version)
        self._next_version += 1
        return True

    def __len__(self) -> int:
        return len(self._buf)

    @property
    def models(self) -> list[Any]:
        """Newest-first list of buffered global models."""
        return list(reversed(self._buf))

    @property
    def versions(self) -> list[int]:
        """Newest-first version ids, aligned with ``models``."""
        return list(reversed(self._versions))

    def fused(self) -> Any:
        """FedGKD ensemble teacher  w̄_t = mean of buffer."""
        assert self._buf, "empty buffer"
        return ensemble_average(list(self._buf))
