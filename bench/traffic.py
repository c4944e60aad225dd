"""The one traffic generator: which clients a round trains, and how much.

A traffic mix is a data file ``bench/traffic/<name>.json``:

``algorithm``       the federated algorithm (``repro.core.algorithms``)
``executor``        the executor route ``run_federated`` is given
``warmup_rounds``   rounds of set-up before the window, which compile or
                    load what every cohort runs (the round body, eval)
``compare_rounds``  the first rounds, which the reference follows
                    (``bench.correct``); the window stays open until they
                    have run
``trace_rounds``    rounds profiled after the window in a ``--trace 1`` run

Cohorts are the program's own: ``run_federated`` draws each round's K of
the configuration's clients uniformly without replacement
(``FederatedData.sample_cohort``) from its generator, seeded from the run
seed.  ``record_cohorts`` only notes what it drew.  Where a cohort's
largest shard sets a program's shape (the FedGKD teacher precompute pads
every sampled shard to it), a round that draws a new largest shard traces
that program again inside the window, as a user's run does.
"""
from __future__ import annotations

import numpy as np


def local_steps(cfg: dict) -> int:
    """Local steps per client and round: one mean local epoch,
    train_size / n_clients / batch_size."""
    return max(1, round(cfg["train_size"] / cfg["n_clients"]
                        / cfg["batch_size"]))


def cohort_size(cfg: dict) -> int:
    return max(1, int(round(cfg["participation"] * cfg["n_clients"])))


def record_cohorts(fed) -> list[np.ndarray]:
    """Wrap ``fed.sample_cohort`` so that every cohort it draws is also
    appended to the returned list, round by round."""
    drawn: list[np.ndarray] = []
    sample = fed.sample_cohort

    def sample_cohort(rng, k, exclude=None):
        cohort = sample(rng, k, exclude)
        drawn.append(np.asarray(cohort).copy())
        return cohort

    fed.sample_cohort = sample_cohort
    return drawn


def cohort_maxima(cfg: dict, sizes: list[int]) -> list[int]:
    """Every shard size that can be the largest of a cohort: all but the
    K - 1 smallest."""
    return sorted(set(sorted(sizes)[cohort_size(cfg) - 1:]))


def trained_examples(cfg: dict, steps: int, sizes: list[int],
                     cohort) -> int:
    """Real (unmasked) examples a cohort trains in one round: each client
    ``steps`` batches of ``min(B, n)``, as long as its local epochs last."""
    total = 0
    for cid in cohort:
        n = sizes[int(cid)]
        bs = min(cfg["batch_size"], n)
        total += min(steps, cfg["local_epochs"] * -(-n // bs)) * bs
    return total
