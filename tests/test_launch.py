"""Launch-layer functional tests: serial FL LM driver + serving loop."""
import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch import serve as serve_lib
from repro.launch import train as train_lib
from repro.models import transformer


def test_serial_fl_lm_round_runs():
    cfg = get_smoke_config("phi4-mini-3.8b")
    out = train_lib.run_serial(cfg, rounds=1, n_clients=2,
                               batches_per_round=2, batch=2, seq=16,
                               algo="fedgkd", gamma=0.2, buffer_m=2,
                               lr=0.05, verbose=False)
    assert len(out["history"]) == 1
    assert np.isfinite(out["history"][0]["ppl"])


def test_serial_fedavg_vs_fedgkd_same_shapes():
    cfg = get_smoke_config("mamba2-2.7b")
    for algo in ("fedavg", "fedgkd"):
        out = train_lib.run_serial(cfg, rounds=1, n_clients=2,
                                   batches_per_round=1, batch=2, seq=16,
                                   algo=algo, verbose=False)
        assert np.isfinite(out["history"][0]["loss"])


def test_serve_loop_processes_queue():
    cfg = get_smoke_config("phi4-mini-3.8b")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
               for _ in range(5)]
    loop = serve_lib.ServeLoop(cfg, params, batch=2, max_len=32)
    stats = loop.run(prompts, gen=4)
    assert len(stats["outputs"]) == 5
    assert all(len(v) == 4 for v in stats["outputs"].values())
    assert stats["tok_per_s"] > 0


def test_client_batches_are_client_distinct():
    cfg = get_smoke_config("phi4-mini-3.8b")
    data = train_lib.client_batches(cfg, n_clients=3, batches_per_round=1,
                                    batch=4, seq=32, seed=0)
    assert data.shape == (3, 1, 4, 32)
    # different clients draw from different Markov sources
    assert not np.array_equal(data[0], data[1])


def test_compile_cache_dir_is_env_or_fixed_repo_path():
    """With JAX_COMPILATION_CACHE_DIR set nothing is set in code; unset,
    the cache is the fixed, gitignored ``<repo>/.jax_cache``."""
    import pathlib

    from repro.launch import compile_cache

    assert compile_cache.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) is None
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.compile_cache_dir({}) == str(repo / ".jax_cache")
    ignored = (repo / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
