"""dsort_tpu — TPU-native distributed sorting framework with fault tolerance.

A from-scratch JAX / XLA / Pallas re-design of the capabilities of the reference
C system ``khimansusinha/Distributed-sorting-with-fault-tolerance`` (master/worker
merge sort over TCP sockets with reassign-on-failure; see /root/reference,
``server.c`` / ``client.c``):

- the worker's local recursive merge sort (``client.c:140-173``) becomes a
  per-chip jitted sort (``ops.local_sort``);
- the master's socket scatter + centralized O(N*k) merge (``server.c:342-456,
  481-524``) becomes sample-sort splitters + an ``all_to_all`` shuffle over
  the mesh with per-chip merges (``parallel.sample_sort``), plus a
  gather-merge pipeline mirroring the reference shape (``models.pipelines``);
- the fixed 4-worker TCP star (``server.c:120-157``) becomes a
  ``jax.sharding.Mesh`` built from typed config (``config``, ``parallel.mesh``),
  and for cross-host clusters a native C++ framed-TCP coordinator with
  Python/JAX worker shims (``runtime``);
- the reassign-on-failure scheduler (``server.c:297-477``) becomes a
  liveness-tracking scheduler with heartbeats (fixing the reference's
  hang-blindness), whole-shard retry on a live device, result-slot pinning,
  clean job failure when no devices remain, and sorted-shard checkpointing
  for partial recovery (``scheduler``, ``checkpoint``).

Package layout:
  models/     sort pipelines (the "model zoo": local, gather-merge, sample-sort)
  ops/        per-chip kernels (lax.sort wrappers, bitonic network, Pallas tile sort)
  parallel/   mesh construction + SPMD collectives (shard_map / all_to_all)
  scheduler/  job driver, liveness, fault tolerance, fault injection
  data/       ingest/egress + synthetic generators (uniform, zipf, terasort)
  runtime/    native C++ runtime (k-way merge, worker table, TCP coordinator)
  utils/      structured logging, metrics, profiling hooks
  checkpoint  sorted-shard persistence for partial recovery
  cli         dsort run/serve/fleet/gen/terasort/external/validate/...
"""

__version__ = "0.1.0"

# Lazy config re-exports (PEP 562): `config` imports the backend (jnp
# dtypes), and the fleet control plane (`fleet.controller`, ARCHITECTURE
# §12) must be importable in a process that never initializes JAX — so the
# package root cannot import config eagerly.
_CONFIG_NAMES = ("JobConfig", "MeshConfig", "SortConfig", "load_conf_file")


def __getattr__(name):
    if name in _CONFIG_NAMES:
        from dsort_tpu import config

        return getattr(config, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_CONFIG_NAMES))
