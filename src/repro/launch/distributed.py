"""Multi-host bootstrap + persistent compilation cache for sweep fleets.

`initialize_distributed` wraps `jax.distributed.initialize` so a sweep
script becomes multi-process by adding three arguments (or the matching
environment variables) and nothing else:

    initialize_distributed(coordinator_address="10.0.0.1:1234",
                           num_processes=4, process_id=rank)
    mesh = make_sweep_mesh()          # jax.devices() is now GLOBAL:
                                      # the mesh spans every process
    plan = ExecutionPlan(mesh=mesh, chunk_rounds=32)

After initialization `jax.devices()` enumerates every process's devices,
so the existing `make_sweep_mesh` builds a process-spanning mesh with no
new code path — each process then feeds the full host-side batch stream
into `stage_batch_block`, which materializes only that process's
addressable shards (see `launch.mesh.put_with_sharding`).  Called with no
arguments in a single-process job it is a no-op, keeping the
single-process sweep bitwise-identical to the pre-distributed engine.

On CPU backends the default collectives implementation cannot cross
processes ("Multiprocess computations aren't implemented on the CPU
backend"); we switch it to gloo BEFORE initialize, which is what makes
the 2-process CI smoke real.

`setup_compilation_cache` turns on JAX's persistent compilation cache so
a restarted/resumed fleet skips recompiles — the other half of
preemption-safe sweeps next to the engine's chunk-boundary checkpoints.
Where $JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing is
set in code; otherwise the cache lives at one fixed path in the checkout,
`DEFAULT_CACHE_DIR`.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import numpy as np

import jax

#: Environment variable JAX reads its compilation-cache directory from.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The cache's one fixed home in the checkout when $JAX_COMPILATION_CACHE_DIR
#: is unset.  It never moves between runs: a cache is only found again at
#: the path that wrote it.
DEFAULT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                        / ".jax_cache")


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           local_device_ids=None) -> bool:
    """Bootstrap the JAX distributed runtime (idempotent, single-process
    no-op).

    Returns True when a multi-process runtime was (or already is) up,
    False for the single-process no-op.  Arguments default to None so the
    standard cluster-environment variables (JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID, or an auto-detected cluster) can
    fill them in, exactly as `jax.distributed.initialize` documents.

    Explicit num_processes=1 (or an environment resolving to one process)
    skips initialization entirely: single-process stays on the default
    runtime and remains bitwise-identical to a never-distributed run.

    Nothing here touches the XLA backends before `initialize` runs —
    jax refuses to bootstrap after any computation has executed, and even
    `jax.process_count()` would count as one.
    """
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    if num_processes == 1:
        return False
    if (coordinator_address is None and num_processes is None
            and process_id is None
            and "JAX_COORDINATOR_ADDRESS" not in os.environ):
        return False                      # single-process job, nothing to do
    # The default CPU collectives cannot cross processes; gloo can.  Must
    # be set before initialize; a no-op for non-CPU backends.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id,
                               local_device_ids=local_device_ids)
    return jax.process_count() > 1


def setup_compilation_cache() -> str:
    """Enable JAX's persistent compilation cache; returns its directory.

    With $JAX_COMPILATION_CACHE_DIR set this sets nothing: JAX reads that
    variable itself, and the cache goes there and nowhere else.  Unset, the
    cache goes to `DEFAULT_CACHE_DIR` (`.jax_cache` at the checkout root,
    which git ignores).  Entry points call it before their first compile.
    """
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def fetch(x):
    """Host numpy copy of `x`, whether it is process-local or a global
    array sharded across processes (the result fetch edge of a
    multi-process sweep: loss/metric trajectories and final params come
    back fully replicated on every process)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)
