"""What decides ``correct``: the timed run's first rounds against the plain
reference (``bench.reference.fedround``) from the same seed.

The program's first ``compare_rounds`` rounds are the first rounds of the
run the window goes on to time: the same ``run_federated`` call, the same
compiled programs, at the timed sizes.  Five numbers, each with a limit of
its own (``bench/cells/<cell>.json``):

``first_loss_gap``       the lower median over the first round's clients
                         of the relative gap of a client's mean local loss
                         (cross-entropy, plus the KD term under FedGKD)
``first_test_loss_gap``  relative gap of the evaluated test loss after the
                         first round
``client_update_gap``    the first round's client updates (each sampled
                         client's model less the initial one, as the
                         executor hands it to the server): for each client
                         the norm gap of its worst leaf, as below; the
                         lower median over the clients
``update_norm_gap``      the first round's server update (the new global
                         model less the initial one, what the server
                         applies), by the worst leaf: the gap of the two
                         norms of that leaf over the larger of the
                         reference's norm of the leaf and of the median leaf
``change_median_gap``    the same gap for the change of the global model
                         over all compared rounds, of the median leaf

The later rounds' losses and their worst leaf are not compared: 35 local
steps a round at the paper's learning rate amplify the chip's rounding
from round to round, so those numbers swing from seed to seed on sound
runs (PERF.md, Findings).  For the same reason the first round's clients
are compared by their lower median (of 4 clients the second smallest
gap): a client whose loss spikes within its 35 steps (gradient norms
several times the others') carries the rounding through the spike, and
its own gap, and with it the worst leaf of the server's update, reads as
high as the control's; one or two of the 4 clients spike on some seeds.
``per_round`` gives every round's gaps.

A leaf whose reference update is under a thousandth of the median leaf's
is left out of the norm gaps: it moves by rounding alone.
"""
from __future__ import annotations

import numpy as np

NAMES = ("first_loss_gap", "first_test_loss_gap", "client_update_gap",
         "update_norm_gap", "change_median_gap")
NEGLIGIBLE = 1e-3


def lower_median(values) -> float:
    """The middle value, or of two the smaller: of 4 the second smallest."""
    return float(np.sort(values)[(len(values) - 1) // 2])


def _leaf_norms(a, b) -> np.ndarray:
    import jax

    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)
                                          - np.asarray(y, np.float64)))
                     for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def leaf_table(w0, prog: dict, ref: dict) -> dict:
    """Per compared round and leaf: the norm of the program's and the
    reference's change since ``w0`` and of their difference; the leaves'
    names and sizes.  For reading the numbers apart, not for the verdict."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(w0)[0]
    out = {"leaf": [jax.tree_util.keystr(p) for p, _ in flat],
           "size": [int(np.size(v)) for _, v in flat],
           "prog": [], "ref": [], "diff": []}
    for wp, wr in zip(prog["params"], ref["params"]):
        out["prog"].append(list(_leaf_norms(wp, w0)))
        out["ref"].append(list(_leaf_norms(wr, w0)))
        out["diff"].append(list(_leaf_norms(wp, wr)))
    return out


def _worst_leaf_gap(w0, wp, wr) -> float:
    """The norm gap of the worst leaf of ``wp - w0`` against ``wr - w0``,
    over the larger of the reference's norm of that leaf and of the median
    leaf; leaves the reference moves by under a thousandth of the median
    leaf left out."""
    cp, cr = _leaf_norms(wp, w0), _leaf_norms(wr, w0)
    keep = cr >= NEGLIGIBLE * np.median(cr)
    return float(np.max((np.abs(cp - cr) / np.maximum(cr, np.median(cr)))
                        [keep]))


def client_gaps(w0, prog: dict, ref: dict) -> list[float]:
    """Each first-round client's worst-leaf update gap, in cohort order."""
    return [_worst_leaf_gap(w0, p, r) for p, r in
            zip(prog["client_params"], ref["client_params"][0])]


def per_round(w0, prog: dict, ref: dict) -> dict:
    """Every compared round's gaps: the lower median and the worst
    client's loss gap, the test loss gap, and of the global model's change since the
    start the worst leaf's and the median leaf's norm gap."""
    lp, lr = np.asarray(prog["client_loss"]), np.asarray(ref["client_loss"])
    tp, tr = np.asarray(prog["test_loss"]), np.asarray(ref["test_loss"])
    ur = _leaf_norms(ref["params"][0], w0)
    keep = ur >= NEGLIGIBLE * np.median(ur)
    gaps = np.abs(lp - lr) / np.abs(lr)
    out = {"client_loss": [lower_median(g) for g in gaps],
           "client_loss_worst": list(np.max(gaps, axis=1)),
           "test_loss": list(np.abs(tp - tr) / np.abs(tr)),
           "change_worst": [], "change_median": []}
    for wp, wr in zip(prog["params"], ref["params"]):
        cp, cr = _leaf_norms(wp, w0), _leaf_norms(wr, w0)
        gap = (np.abs(cp - cr) / np.maximum(cr, np.median(cr)))[keep]
        out["change_worst"].append(float(np.max(gap)))
        out["change_median"].append(float(np.median(gap)))
    return {k: [float(v) for v in vs] for k, vs in out.items()}


def numbers(w0, prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``client_loss`` (rounds x clients),
    ``test_loss`` (rounds), ``params`` (the global model after each round)
    and ``client_params`` (the program's: the first round's client models;
    the reference's: every round's)."""
    d = per_round(w0, prog, ref)
    return {
        "first_loss_gap": d["client_loss"][0],
        "first_test_loss_gap": d["test_loss"][0],
        "client_update_gap": lower_median(client_gaps(w0, prog, ref)),
        "update_norm_gap": d["change_worst"][0],
        "change_median_gap": d["change_median"][-1],
    }


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per number, its value beside its limit.  A number
    whose limit is ``null`` has no upper reading to hold it against and is
    shown but not compared."""
    shown = {k: {"value": nums[k], "limit": limits[k]} for k in NAMES}
    ok = all(np.isfinite(nums[k]) and nums[k] <= limits[k] for k in NAMES
             if limits[k] is not None)
    return ok, shown
