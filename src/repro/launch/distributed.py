"""Real multi-process jax topologies for the multi-host federated loop.

The population placement layer (``repro.population.placement``) is
transport-only — two plain processes over a shared exchange dir already
train in lockstep.  This module is the step beyond the emulator: bring the
SAME processes up as one ``jax.distributed`` topology, so collectives,
``jax.process_count()``-aware mesh selection
(``executor.ShardMapExecutor`` shards each host's cohort slice over
``jax.local_devices()``) and the process-local global-array stitch
(``sharding.make_array_from_process_local_data_compat`` across
processes) all run for real.

Typical 2-host CPU launch (each process forcing 2 host devices; the CLI
smoke pins ``JAX_PLATFORMS=cpu`` itself, so its ranks never contend for
an accelerator):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
    python -m repro.launch.distributed \\
        --coordinator 127.0.0.1:<port> --num-processes 2 --process-id 0 &
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
    python -m repro.launch.distributed \\
        --coordinator 127.0.0.1:<port> --num-processes 2 --process-id 1

On CPU the cross-process collectives need the gloo backend
(``jax_cpu_collectives_implementation``); on TPU/GPU jax picks its native
transport and the knob is ignored.  ``initialize`` must run before any
other jax call touches the backend — first device access freezes the
topology.
"""
from __future__ import annotations

import socket
from typing import Optional


def find_free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (for the coordinator of test
    topologies; production launchers get the address from the scheduler)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, *,
               cpu_collectives: Optional[str] = "gloo") -> dict:
    """Bring this process up as one rank of a ``jax.distributed`` topology.

    Wraps ``jax.distributed.initialize`` with the one piece of setup a CPU
    topology needs — selecting the gloo collectives implementation — which
    must happen BEFORE the backend initializes.  Releases without the knob
    (or without gloo builds) just skip it: the shim degrades, it never
    blocks a real accelerator topology.

    Returns a summary dict (process index/count, local/global device
    counts) so launchers and tests can assert the topology they asked for
    actually came up.
    """
    import jax

    if cpu_collectives is not None:
        try:
            jax.config.update("jax_cpu_collectives_implementation",
                              cpu_collectives)
        except (AttributeError, ValueError):
            pass        # older jax: single-process CPU still works
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return {"process_id": jax.process_index(),
            "process_count": jax.process_count(),
            "local_devices": len(jax.local_devices()),
            "global_devices": len(jax.devices())}


def placement_from_runtime(exchange_dir: str, **kw):
    """A ``HostPlacement`` for THIS process's rank in the live topology.

    Call after ``initialize``: host identity then comes from the one
    source of truth (``jax.process_index`` / ``jax.process_count``)
    instead of being threaded through argv twice — a transposed rank
    would silently swap shard ownership between hosts."""
    import jax

    from repro.population.placement import HostPlacement

    return HostPlacement(jax.process_index(), jax.process_count(),
                         exchange_dir=exchange_dir, **kw)


def _smoke(args) -> int:
    """CLI smoke: initialize, psum a rank-tagged array across processes,
    verify every rank sees the same total.  Exit 0 = the topology works."""
    import numpy as np

    info = initialize(args.coordinator, args.num_processes, args.process_id,
                      cpu_collectives=args.cpu_collectives or None)
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_clients_mesh
    from repro.sharding import make_array_from_process_local_data_compat

    mesh = make_clients_mesh()
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("clients"))
    n_local = info["local_devices"]
    n_global = info["global_devices"]
    local = (np.arange(n_local, dtype=np.float32)
             + info["process_id"] * n_local)
    arr = make_array_from_process_local_data_compat(sharding, local,
                                                    (n_global,))
    total = float(jax.jit(jnp.sum)(arr))
    want = float(np.arange(n_global, dtype=np.float32).sum())
    print(f"[distributed] rank {info['process_id']}/{info['process_count']} "
          f"local_devices={n_local} global_devices={n_global} "
          f"sum={total} want={want}")
    return 0 if total == want else 1


def main(argv=None) -> int:
    import argparse
    import os

    # the smoke is a CPU topology: set before jax first touches a backend
    os.environ["JAX_PLATFORMS"] = "cpu"
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--coordinator", required=True,
                    help="coordinator address, host:port (rank 0 binds it)")
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--cpu-collectives", default="gloo",
                    help="jax_cpu_collectives_implementation ('' to skip)")
    return _smoke(ap.parse_args(argv))


if __name__ == "__main__":
    raise SystemExit(main())
