"""The benchmark's own data: synthetic images and the Dirichlet split.

Copied from the program's ``repro.data.synthetic.SyntheticImageTask`` and
``repro.data.dirichlet.dirichlet_partition`` so that no later change to
the program can move the yardstick.  Two departures, both for the
benchmark's sake and neither a change of the method:

* the per-example circular shift is applied through 25 pre-rolled class
  templates instead of a Python loop over examples, and contrast, shift
  and noise are drawn on the device with ``jax.random`` in blocks, then
  copied into one host float32 array (set-up time and host memory at
  90,000 Tiny-ImageNet-sized images);
* the labels and the partition come from the configuration's fixed
  ``partition_seed``, not from the run's seed, so every seed trains on the
  same set of client sizes; the run's seed draws the class templates,
  the images, the weights, the batch order and the order of the cohorts.
"""
from __future__ import annotations

import functools

import numpy as np

BLOCK = 8192          # images generated per block
SHIFT = 2             # circular shifts are drawn from [-SHIFT, SHIFT]^2


def image_templates(rng: np.random.Generator, num_classes: int, hw: int,
                    channels: int):
    """Low-frequency class templates (random Fourier features) and a
    shift-invariant per-class channel bias."""
    yy, xx = np.meshgrid(np.linspace(0, 1, hw), np.linspace(0, 1, hw),
                         indexing="ij")
    templates = np.zeros((num_classes, hw, hw, channels), np.float32)
    for k in range(num_classes):
        for ch in range(channels):
            for _ in range(3):
                fx, fy = rng.uniform(0.5, 3.0, 2)
                ph = rng.uniform(0, 2 * np.pi)
                templates[k, :, :, ch] += np.sin(
                    2 * np.pi * (fx * xx + fy * yy) + ph)
    templates /= np.sqrt((templates ** 2).mean((1, 2, 3), keepdims=True)
                         + 1e-8)
    chan_bias = rng.normal(0, 0.5, size=(num_classes, 1, 1, channels)
                           ).astype(np.float32)
    return templates, chan_bias


@functools.cache
def _block_fn():
    """One jitted block of images.  Everything the seed draws (the key, the
    templates, the bias) is an argument, not a constant of the program, so
    one program serves every seed and is found in the persistent cache."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(key, i, lab, rolled, bias, noise):
        kc, ks, kn = jax.random.split(jax.random.fold_in(key, i), 3)
        m = lab.shape[0]
        contrast = jax.random.uniform(kc, (m, 1, 1, 1), jnp.float32,
                                      0.6, 1.4)
        shift = jax.random.randint(ks, (m,), 0, rolled.shape[1])
        x = rolled[lab, shift] * contrast + bias[lab]
        return x + noise * jax.random.normal(kn, x.shape, jnp.float32)

    return block


def images(key, labels: np.ndarray, templates, chan_bias,
           noise: float) -> np.ndarray:
    """template(label) x contrast, circularly shifted, + channel bias +
    Gaussian noise: (n, hw, hw, channels) float32 on the host.  Drawn on
    the default device in blocks of ``BLOCK`` images, one jitted call each,
    and copied back into one host array."""
    import jax.numpy as jnp

    shifts = [(a, b) for a in range(-SHIFT, SHIFT + 1)
              for b in range(-SHIFT, SHIFT + 1)]
    rolled = jnp.asarray(np.stack(
        [np.roll(templates, s, axis=(1, 2)) for s in shifts], axis=1))
    bias = jnp.asarray(chan_bias)
    noise = jnp.float32(noise)
    block = _block_fn()
    n = len(labels)
    out = np.empty((n,) + templates.shape[1:], np.float32)
    for i, lo in enumerate(range(0, n, BLOCK)):
        hi = min(n, lo + BLOCK)
        lab = np.zeros(BLOCK, np.int32)      # one shape: one compile
        lab[:hi - lo] = labels[lo:hi]
        out[lo:hi] = np.asarray(block(key, i, jnp.asarray(lab), rolled, bias,
                                      noise))[:hi - lo]
    return out


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float,
                        seed: int, min_per_client: int = 2
                        ) -> list[np.ndarray]:
    """Per-client index arrays, a disjoint cover of ``labels``: for each
    class a Dir(alpha) draw over the clients decides each client's share
    (Hsu et al. 2019), as the paper partitions."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    client_idx: list[list[int]] = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.nonzero(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for k, part in enumerate(np.split(idx, cuts)):
            client_idx[k].extend(part.tolist())
    for k in np.argsort([len(ci) for ci in client_idx]):
        while len(client_idx[k]) < min_per_client:
            donor = int(np.argmax([len(ci) for ci in client_idx]))
            client_idx[k].append(client_idx[donor].pop())
    out = [np.array(sorted(ci), dtype=np.int64) for ci in client_idx]
    assert sum(len(o) for o in out) == len(labels)
    return out


def fixed_split(cfg: dict):
    """Labels of the train and test sets and the client partition: all
    drawn from ``cfg["partition_seed"]``, the same for every run seed."""
    rng = np.random.default_rng(cfg["partition_seed"])
    c = cfg["num_classes"]
    train_y = rng.integers(0, c, size=cfg["train_size"])
    test_y = rng.integers(0, c, size=cfg["test_size"])
    parts = dirichlet_partition(train_y, cfg["n_clients"],
                                cfg["dirichlet_alpha"],
                                seed=cfg["partition_seed"])
    return train_y, test_y, parts


def client_sizes(cfg: dict) -> list[int]:
    return [len(p) for p in fixed_split(cfg)[2]]


def make_arrays(cfg: dict, seed: int):
    """(client shards [(x, y)], test_x, test_y) for one run seed.  The
    train images are generated in client order, so each shard is a view
    of one array and nothing is copied."""
    import jax

    train_y, test_y, parts = fixed_split(cfg)
    rng = np.random.default_rng(seed)
    templates, chan_bias = image_templates(rng, cfg["num_classes"],
                                           cfg["image_hw"], cfg["channels"])
    k_train, k_test = jax.random.split(
        jax.random.key(int(rng.integers(2 ** 31))))
    train_y = train_y[np.concatenate(parts)]
    train_x = images(k_train, train_y, templates, chan_bias, cfg["noise"])
    test_x = images(k_test, test_y, templates, chan_bias, cfg["noise"])
    bounds = np.cumsum([0] + [len(p) for p in parts])
    shards = [(train_x[lo:hi], train_y[lo:hi])
              for lo, hi in zip(bounds[:-1], bounds[1:])]
    return shards, test_x, test_y
