"""The paper's primary contribution: I/O-efficient (here: SIMD/pod-native)
k-bisimulation partition construction and maintenance for massive graphs.

Public API:
  build_bisim              — Algorithm 1 on one device (3 signature modes)
  build_bisim_distributed  — Algorithm 1 over a device mesh (shard_map)
  BisimMaintainer          — Algorithms 2-4 (+ deletions, change-k)
  oracle_pids              — exact Definition-1 oracle for validation

Device execution model
======================
Everything device-side is built around one rule: **dispatch and sync
counts are part of the contract**, not an implementation detail.  Host
round-trips — not FLOPs — dominate at the frontier/graph sizes the paper
benchmarks, so each path documents how many XLA program launches and
device->host transfers it performs, and the tracer (`repro.obs`) emits a
``build.dispatch`` or ``maint.dispatch`` event at every launch and opens a
``build.sync`` or ``maint.sync`` span around every transfer, so tests and
benchmarks can count both and time the host's wait.

* **Fused build** (``build_bisim(fused=True)``, the default without
  per-level stores): the entire k-iteration loop runs inside a single
  jitted ``lax.while_loop`` program — exactly ONE dispatch and ONE
  device->host sync (the final history fetch) per build, at any k.
* **Staged build** (``with_store=True`` or ``fused=False``): one fused
  signature->rank program per iteration, draining scalars every
  ``sync_every`` iterations.
* **Fused maintenance** (``propagate_levels_resident``): all k levels of
  the frontier fold + store probe unroll into ONE jitted program; in
  the steady state (no partition change) a whole propagate costs one
  gather, one upload, one dispatch and one k-vector scalar sync.  The
  first level that actually changes mints its novel pids on the host
  and merges them into the device store; later levels fall back down
  the ladder.
* **Fallback ladder**: fused k-loop -> per-level device-fused
  (``resident_level_resolve``) -> staged device (probe/resolve/merge as
  separate programs) -> pure host.  Every rung is bit-identical to the
  host reference (asserted by tests/test_fused_build.py and the update
  fuzz harness); a transient device fault (`TransientIOError`)
  permanently degrades the maintainer to the host rung, never changes
  results, and any other device error propagates.
* **Bucketing policy**: all device batch shapes are padded to
  ``device_maint.bucket(n)`` — the next power of two, floored at
  ``BUCKET_FLOOR`` — so padding waste stays under 2x while the compiled
  program cache stays O(log max_n) entries per call site.
"""
from .partition import (BisimResult, IterationStats, bisim_step, build_bisim,
                        partition_blocks, refines, same_partition)
from .distributed import (ShardedGraph, build_bisim_distributed,
                          make_flat_mesh, shard_graph)
from .device_maint import DeviceSigStore, frontier_fold
from .maintenance import (BisimMaintainer, InMemoryBackend,
                          MaintenanceBackend, MaintenanceReport)
from .faults import (FaultPlan, InjectedCrash, TransientIOError,
                     install_fault_plan, with_retries)
from .integrity import ChecksumError, crc32_array, verify_npy
from .oracle import is_k_bisimilar, oracle_pids
from .sig_store import (SigStore, SpillableSigStore, fuse_key, label_key,
                        split_key)
from . import hashes_np, signatures

__all__ = [
    "BisimResult", "IterationStats", "bisim_step", "build_bisim",
    "partition_blocks", "refines", "same_partition", "ShardedGraph",
    "build_bisim_distributed", "make_flat_mesh", "shard_graph",
    "BisimMaintainer", "InMemoryBackend", "MaintenanceBackend",
    "MaintenanceReport", "DeviceSigStore", "frontier_fold",
    "is_k_bisimilar", "oracle_pids", "SigStore", "SpillableSigStore",
    "fuse_key", "label_key", "split_key", "hashes_np", "signatures",
    "FaultPlan", "InjectedCrash", "TransientIOError", "install_fault_plan",
    "with_retries", "ChecksumError", "crc32_array", "verify_npy",
]
