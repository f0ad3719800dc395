"""Maintenance of an existing k-bisimulation partition (paper §4, Alg. 2-4).

The module is split into an *update-semantics core* and a *storage backend
protocol*:

  * `BisimMaintainer` owns what the paper's Algorithms 2-4 actually say:
    per-level frontier evolution (the STXXL priority queue of
    (iteration, nId) pairs becomes processing frontier[j] level by level;
    "propagate changes to pQueue", line 20 of Alg. 4, becomes
    frontier[j+1] |= parents(changed)), tombstone bookkeeping for
    DELETE_NODE, `compact`, the §4.2 switch-back-to-Build_Bisim heuristic
    (`rebuild_threshold`), and Change-k.

  * `MaintenanceBackend` is everything storage: where the pid history
    pId_0..pId_k lives, how a frontier's out-edges are gathered, how
    signatures resolve against the store S, and how graph mutations hit
    the N_t/E_t tables.  Two implementations exist: `InMemoryBackend`
    below (CSR arrays + array-backed `SigStore`, the fast path) and
    `repro.exmem.maintenance.OocBackend` (chunked on-disk tables +
    `SpillableSigStore`, sequential merge joins against the sorted
    per-level pid files — maintenance for graphs that needed
    `build_bisim_oocore`).

The core is backend-agnostic: the same update stream over either backend
yields identical partitions up to pid renaming, because both resolve the
bit-identical signature hashes (`hashes_np` mirrors the JAX lanes) against
per-level stores sharing one schema.

Signature modes: the paper's set semantics (`sorted` / `dedup_hash`, which
hash identically here) plus `multiset` — counting bisimulation, maintained
by skipping the (eLabel, pId) dedup exactly as construction does.

Device-resident propagation (``BisimMaintainer(..., device=True)``): the
two hot pieces of `_propagate` — the frontier signature fold and the
store resolve — move onto the accelerator through `core.device_maint`.
The contract:

  * what runs on device — the frontier signature fold
    (`frontier_signatures_device`, one jitted program per power-of-two
    shape bucket, constants cached on device across levels) and, for
    backends that mirror their stores (`InMemoryBackend`), the S_j
    probe + first-occurrence minting + merge-insert (`DeviceSigStore`,
    donated sorted columns).  `OocBackend` folds on device after its
    sequential merge-join gather and keeps resolving through the
    spillable host store (S must outgrow RAM there by design).
  * stage placement is adaptive (`device_maint`): the dedup sort and
    the segment wrap-sum run in-program on accelerators but through
    numpy on CPU backends (XLA CPU's comparator sort and sequential
    prefix sum measurably lose to lexsort/np.add.at, the fused per-edge
    hash measurably wins) — overridable per call, bit-identical either
    way.
  * what stays on host — frontier bookkeeping (np.unique / union1d),
    parent gathers, graph mutations, and every I/O pass; the per-level
    host traffic is the resolved frontier pids (needed for the changed
    mask) plus one minted-count scalar.
  * the fallback — backends without the capability (`enable_device`
    returning False) silently stay on the vectorized numpy path, which
    also remains the differential reference.
  * the bit-parity invariant — device and host propagation produce
    bit-identical pid histories, next_pid sequences and (for disk
    backends) IOStats over any update stream: the device fold replays
    the exact `hashes_np` lanes and `DeviceSigStore.get_or_assign_pairs`
    replays `SigStore.get_or_assign` minting order.  The differential
    fuzz harness (`tests/test_update_fuzz.py`) asserts this after every
    update of randomized streams.
"""
from __future__ import annotations

import abc
import contextlib
import dataclasses
import time
import warnings
from typing import Iterable, Optional

import numpy as np

from repro.graph.storage import Graph
from . import hashes_np
from .faults import TransientIOError, fault_point
from .partition import BisimResult, bisim_step, build_bisim
from .sig_store import SigStore, fuse_key, label_key
from ..obs import tracer as obs


@dataclasses.dataclass
class MaintenanceReport:
    """Per-update statistics (the quantities of paper Figs. 7-8).

    The per-level lists always have exactly k entries — levels the
    propagation never reached (empty frontier, or the §4.2 rebuild
    heuristic firing mid-loop) hold zeros — so report consumers may
    index by level unconditionally.
    """
    nodes_checked: list          # per level j=1..k
    nodes_changed: list          # per level
    partitions_touched: list     # per level
    rebuilt: bool = False
    level_seconds: list = dataclasses.field(default_factory=list)
    device: bool = False         # device propagation path taken

    def as_dict(self) -> dict:
        """Uniform stats surface (same contract as `IOStats.as_dict` /
        `AioStats.as_dict`)."""
        return {
            "nodes_checked": [int(x) for x in self.nodes_checked],
            "nodes_changed": [int(x) for x in self.nodes_changed],
            "partitions_touched": [int(x) for x in
                                   self.partitions_touched],
            "rebuilt": bool(self.rebuilt),
            "level_seconds": [float(x) for x in self.level_seconds],
            "device": bool(self.device),
        }

    def merge(self, other) -> "MaintenanceReport":
        """Fold another report (or its `as_dict()`) into this one, in
        place: per-level lists add elementwise (padded to the longer k),
        `rebuilt` ORs, `device` ANDs (True only if every merged update
        ran on device)."""
        d = other.as_dict() if hasattr(other, "as_dict") else dict(other)

        def _add(mine: list, theirs: list) -> list:
            out = [0] * max(len(mine), len(theirs))
            for i, v in enumerate(mine):
                out[i] += v
            for i, v in enumerate(theirs):
                out[i] += v
            return out

        self.nodes_checked = _add(self.nodes_checked,
                                  d.get("nodes_checked", []))
        self.nodes_changed = _add(self.nodes_changed,
                                  d.get("nodes_changed", []))
        self.partitions_touched = _add(self.partitions_touched,
                                       d.get("partitions_touched", []))
        self.level_seconds = _add(self.level_seconds,
                                  d.get("level_seconds", []))
        self.rebuilt = bool(self.rebuilt or d.get("rebuilt", False))
        self.device = bool(self.device and d.get("device", False))
        return self


# the CSR frontier gather is shared with the batch signature path
_csr_gather = hashes_np.csr_gather


class MaintenanceBackend(abc.ABC):
    """Storage contract between `BisimMaintainer` and its state.

    A backend owns four things and nothing else:

      graph tables   — N_t and both E_t sort orders, mutated by
                       `add_node_rows` / `add_edge_rows` /
                       `remove_edge_rows` / `compact`;
      pid history    — one pId_j column per level, read and written
                       through `pid_at` / `set_pid_at` / `pid_column` /
                       `append_pid_rows`;
      signature store — one store S_j per level (level 0 keyed by node
                       label), consulted through `resolve`, which mints
                       dense pids for novel signatures;
      gathers        — `frontier_signatures` (sig_j hash pairs of a
                       frontier from its out-edges and pId_{j-1}),
                       `parents_of` (in-edge sources of changed nodes)
                       and `incident_edges` (DELETE_NODE's edge set).

    Every `nodes` argument below is a sorted, deduplicated int64 id array
    (frontiers come from `np.unique`/`np.union1d`); out-of-core backends
    rely on that ordering to turn pid-file accesses into sequential
    merge joins.  Mutators must validate *before* mutating: a rejected
    update (id out of range) must leave the backend untouched, because the
    core's tombstone re-animation runs only after the backend accepts.

    Besides the abstract methods, every backend exposes three pieces of
    state after `build()` (annotated below; `BisimMaintainer` re-exports
    them as properties): `graph` — the maintained graph, materialized on
    demand by disk backends; `stores` — the per-level signature store
    list; `next_pid` — the next free pid per level.  A backend holding
    its pid history as live in-RAM arrays may additionally expose `pids`
    (list of int64 columns), which the maintainer's `pids` property
    returns directly instead of copying through `pid_column`.
    """

    graph: Graph        # maintained graph (disk backends: materialized)
    stores: list        # signature store S_j per level
    next_pid: list      # next free pid per level

    # ------------------------------------------------------------ geometry
    @property
    @abc.abstractmethod
    def num_nodes(self) -> int: ...

    @property
    @abc.abstractmethod
    def num_edges(self) -> int: ...

    # ------------------------------------------------------------- (re)build
    @abc.abstractmethod
    def build(self, k: int, mode: str, *,
              result: Optional[BisimResult] = None) -> None:
        """Full Build_Bisim of the current graph: k+1 pid levels + stores.
        `result` optionally injects a pre-computed `with_store=True` build
        (in-memory backend only)."""

    # ---------------------------------------------------------- pid history
    @abc.abstractmethod
    def pid_column(self, j: int) -> np.ndarray:
        """The full pId_j column (int64 [N]); in-memory backends return
        their live array, disk backends a materialized copy."""

    @abc.abstractmethod
    def pid_at(self, j: int, nodes: np.ndarray) -> np.ndarray: ...

    @abc.abstractmethod
    def set_pid_at(self, j: int, nodes: np.ndarray,
                   values: np.ndarray) -> None: ...

    @abc.abstractmethod
    def append_pid_rows(self, j: int, values: np.ndarray) -> None: ...

    # ---------------------------------------------------------------- store
    @abc.abstractmethod
    def resolve(self, j: int, keys: np.ndarray) -> np.ndarray:
        """Bulk get-or-assign against S_j (Alg. 4 lines 13-17): resolve
        fused signature keys to pids, minting dense fresh pids for novel
        keys in first-occurrence order."""

    # ---------------------------------------------------- device capability
    def enable_device(self) -> bool:
        """Opt into device-resident propagation.  Returns False when the
        backend has no device path (the maintainer then stays on the host
        fallback); backends that return True must implement
        `frontier_signatures_device`."""
        return False

    def frontier_signatures_device(self, j: int, frontier: np.ndarray, *,
                                   dedup: bool = True):
        """Device sibling of `frontier_signatures`: (hi, lo) *device* u32
        arrays, bucket-padded past ``frontier.size`` (garbage tail).
        None signals the capability is absent and the caller must take
        the host path."""
        return None

    def resolve_pairs(self, j: int, hi, lo, count: int) -> np.ndarray:
        """`resolve` over bucket-padded (hi, lo) hash lanes (only the
        first `count` are real) — the device fold feeds this without a
        host round-trip.  Default: fuse on host and resolve there."""
        with obs.span("maint.sync", what="fold_pairs", keys=count):
            hi, lo = np.asarray(hi)[:count], np.asarray(lo)[:count]
        return self.resolve(j, fuse_key(hi, lo))

    def propagate_level_device(self, j: int, frontier: np.ndarray, *,
                               dedup: bool = True):
        """One device propagation level: fold + resolve.  Default
        composes the two capability methods; backends that mirror their
        store on device may fuse both into a single program.  None when
        the capability is absent."""
        pair = self.frontier_signatures_device(j, frontier, dedup=dedup)
        if pair is None:
            return None
        return self.resolve_pairs(j, pair[0], pair[1], frontier.size)

    def propagate_level_resident(self, j: int, frontier: np.ndarray, *,
                                 dedup: bool = True):
        """The fully-fused device level (fold + probe + mint + changed
        mask in one dispatch, scalars-only sync in the steady state).
        Returns None when the capability is absent — the maintainer then
        falls through to `propagate_level_device`, then to the host path
        (the fallback ladder device-fused -> device-staged -> host) —
        else ``(pj int64 [f] | None, changed bool [f] | None,
        n_changed)`` where the arrays are None iff n_changed == 0."""
        return None

    def propagate_levels_resident(self, frontier: np.ndarray, *,
                                  dedup: bool = True):
        """ALL k levels as one device dispatch (the fused k-loop): valid
        while nothing changes, which is exactly the regime where
        per-level dispatch overhead dominates.  Returns None when the
        capability is absent, else ``(nclean, dirty)`` where the first
        ``nclean`` levels are confirmed unchanged and ``dirty`` is
        either None (every level clean) or the per-level resident-result
        triple for level ``nclean + 1``; the maintainer re-runs any
        remaining levels through the per-level ladder, whose inputs the
        change invalidated."""
        return None

    # -------------------------------------------------------------- gathers
    @abc.abstractmethod
    def frontier_signatures(self, j: int, frontier: np.ndarray, *,
                            dedup: bool = True):
        """(hi, lo) u32 sig_j hash pairs of `frontier` from its out-edges'
        (eLabel, pId_{j-1}(tgt)) pairs and pId_0 — bit-identical to what
        construction stored in S_j."""

    @abc.abstractmethod
    def parents_of(self, nodes: np.ndarray) -> np.ndarray:
        """Sorted unique in-edge sources of `nodes` (uses E_tts)."""

    @abc.abstractmethod
    def incident_edges(self, nid: int):
        """(src, elabel, dst) arrays of every edge touching node `nid`."""

    # ------------------------------------------------------------ mutations
    @abc.abstractmethod
    def add_node_rows(self, labels: np.ndarray) -> int:
        """Append isolated nodes to N_t; returns the first new node id."""

    @abc.abstractmethod
    def add_edge_rows(self, src, elabel, dst) -> None: ...

    @abc.abstractmethod
    def remove_edge_rows(self, src, elabel, dst) -> None: ...

    @abc.abstractmethod
    def compact(self, keep: np.ndarray, remap: np.ndarray) -> None:
        """Drop the rows where ~keep from N_t, E_t and every pid level,
        remapping edge endpoints with the (monotone) `remap`."""

    def out_edges_of(self, nodes: np.ndarray):
        """(src, elabel, dst) of every out-edge of the sorted-unique
        `nodes`, in the canonical (src, elabel, dst) order — the gather
        the quotient service patches touched blocks' rows from.
        Backends override with their E_tst index; this fallback filters
        `incident_edges` per node."""
        nodes = np.asarray(nodes, dtype=np.int64)
        srcs, labs, dsts = [], [], []
        for nid in nodes.tolist():
            s, l, t = self.incident_edges(int(nid))
            m = s == nid
            srcs.append(s[m])
            labs.append(l[m])
            dsts.append(t[m])
        if not srcs:
            e = np.empty(0, np.int32)
            return e, e.copy(), e.copy()
        return (np.concatenate(srcs), np.concatenate(labs),
                np.concatenate(dsts))

    def node_labels_of(self, nodes: np.ndarray) -> np.ndarray:
        """Node labels of the given (sorted) node ids."""
        return np.asarray(self.graph.node_labels)[
            np.asarray(nodes, dtype=np.int64)]

    # -------------------------------------------------------------- change k
    @abc.abstractmethod
    def truncate_k(self, new_k: int) -> None:
        """Slice pid history and stores down to levels 0..new_k."""

    @abc.abstractmethod
    def extend_k(self, new_k: int, mode: str) -> None:
        """Grow to new_k levels (extra Build_Bisim iterations on top of
        the stored state, or a rebuild where that is the cheaper/only
        option — the partition is identical either way)."""

    # ------------------------------------------------------------ durability
    # Durable backends (OocBackend with wal=True) override these; the
    # defaults describe a volatile backend with nothing to log or restore.
    wal_supported: bool = False

    def wal_append(self, op: str, arrays: dict) -> int:
        """Append one logical update to the backend's write-ahead log;
        returns its lsn.  Only meaningful when `wal_supported`."""
        raise NotImplementedError("backend has no write-ahead log")

    def wal_flush(self) -> None:
        """Force every appended-but-uncommitted WAL record durable."""

    def wal_replay_records(self, after_lsn: int = 0):
        """Yield (lsn, op, arrays) for committed WAL records past
        `after_lsn`, in lsn order.  Volatile backends yield nothing."""
        return iter(())

    def snapshot(self, state: dict) -> None:
        """Persist the full maintained state (pid history, stores, graph
        tables, plus the maintainer-owned `state` dict) as a durable,
        manifest-committed artifact that a later `restore` can reopen."""
        raise NotImplementedError("backend has no snapshot support")


class InMemoryBackend(MaintenanceBackend):
    """RAM-resident backend: `Graph` + CSR indexes, mutable int64 pid
    columns, and the array-backed `SigStore` per level — shared verbatim
    with `build_bisim(with_store=True)`.

    Every gather is a batch array operation: frontier signatures come from
    the vectorized `node_signatures_batch` machinery (CSR gather + segment
    combine), resolution is one bulk `SigStore.get_or_assign`, and
    parent propagation is a vectorized gather over the in-CSR.  No
    per-node Python loops on the propagation path.

    With `enable_device()` the per-level stores are mirrored into
    `DeviceSigStore`s (sorted columns as donated device arrays) which
    become authoritative: every resolve — propagation and `add_nodes`
    alike — runs the device probe/mint/merge-insert, and the host
    `SigStore`s the `stores` property returns are lazy re-extractions.
    """

    def __init__(self, graph: Graph):
        self.graph = graph
        self._device = False
        self._store_on_device = False
        self._dstores: Optional[list] = None
        self._stores: Optional[list] = None
        self._fold_cache: dict = {}
        self._resident_cache: dict = {}

    # ----------------------------------------------------- device capability
    def enable_device(self, store_on_device: bool = True) -> bool:
        """Switch propagation onto the device.  ``store_on_device=False``
        keeps the S_j probe/mint on the host `SigStore` (only the fold
        moves off-host, the OocBackend arrangement) — pids are
        bit-identical either way, and the first decision is sticky
        across rebuilds."""
        if not self._device:
            self._device = True
            self._store_on_device = bool(store_on_device)
            if self._stores is not None and self._store_on_device:
                self._mirror_stores()
        return True

    def _mirror_stores(self) -> None:
        from .device_maint import DeviceSigStore
        self._dstores = [DeviceSigStore(s) for s in self._stores]
        # the mirrors are authoritative from here on: drop the host list
        # rather than keep silently-stale entries alive (the `stores`
        # property re-materializes from the mirrors on demand)
        self._stores = None

    @property
    def stores(self) -> list:
        """Per-level stores; in device mode each is lazily re-materialized
        from the authoritative device mirror."""
        if self._dstores is not None:
            return [d.to_host() for d in self._dstores]
        return self._stores

    # ------------------------------------------------------------ geometry
    @property
    def num_nodes(self) -> int:
        return self.graph.num_nodes

    @property
    def num_edges(self) -> int:
        return self.graph.num_edges

    # ------------------------------------------------------------- (re)build
    def build(self, k: int, mode: str, *,
              result: Optional[BisimResult] = None) -> None:
        res = result if result is not None else build_bisim(
            self.graph, k, mode=mode, early_stop=False, with_store=True)
        if res.stores is None:
            raise ValueError("BisimMaintainer needs with_store=True results")
        # pid history as mutable int64 (new pids can exceed int32 eventually)
        self.pids = [np.array(res.pids[j], dtype=np.int64)
                     for j in range(k + 1)]
        self._stores = res.stores    # list[SigStore]; [0] keyed by label
        self.next_pid = list(res.next_pid)
        self._refresh_indexes()
        if self._device and self._store_on_device:
            self._mirror_stores()    # a rebuild re-mirrors from scratch

    def _refresh_indexes(self) -> None:
        self.out_off = self.graph.out_offsets()
        self.in_ord = self.graph.in_order()
        self.in_off = self.graph.in_offsets()
        # every graph mutation funnels through here: drop the fold
        # batch's cached device constants (labels/bounds/pId_0)
        self._fold_cache = {}
        self._resident_cache = {}

    # ---------------------------------------------------------- pid history
    def pid_column(self, j: int) -> np.ndarray:
        return self.pids[j]

    def pid_at(self, j: int, nodes: np.ndarray) -> np.ndarray:
        return self.pids[j][nodes]

    def set_pid_at(self, j: int, nodes: np.ndarray,
                   values: np.ndarray) -> None:
        self.pids[j][nodes] = values

    def append_pid_rows(self, j: int, values: np.ndarray) -> None:
        self.pids[j] = np.concatenate(
            [self.pids[j], np.asarray(values, dtype=np.int64)])

    # ---------------------------------------------------------------- store
    def resolve(self, j: int, keys: np.ndarray) -> np.ndarray:
        if self._dstores is not None:
            out, self.next_pid[j] = self._dstores[j].get_or_assign_keys(
                keys, self.next_pid[j])
            return out
        out, self.next_pid[j] = self._stores[j].get_or_assign(
            keys, self.next_pid[j])
        return out

    def resolve_pairs(self, j: int, hi, lo, count: int) -> np.ndarray:
        if self._dstores is not None:
            out, self.next_pid[j] = self._dstores[j].get_or_assign_pairs(
                hi, lo, count, self.next_pid[j])
            return out
        return super().resolve_pairs(j, hi, lo, count)

    # -------------------------------------------------------------- gathers
    def _gather_frontier(self, j: int, frontier: np.ndarray):
        """(pid0, seg, elabel, pid_tgt) of the frontier's out-edges — the
        shared input of the host and device signature folds."""
        pid_prev = self.pids[j - 1]
        idx, seg = _csr_gather(self.out_off, frontier)
        return (self.pids[0][frontier], seg, self.graph.elabel[idx],
                pid_prev[self.graph.dst[idx]])

    def frontier_signatures(self, j: int, frontier: np.ndarray, *,
                            dedup: bool = True):
        # gather only the frontier's out-edges (cost O(frontier edges),
        # not O(|E|)) and resolve their targets' pId_{j-1}
        p0, seg, lab, pid_tgt = self._gather_frontier(j, frontier)
        return hashes_np.signatures_from_edges(
            p0, seg, lab, pid_tgt, frontier.size, dedup=dedup)

    def _frontier_bounds(self, frontier: np.ndarray) -> np.ndarray:
        """Segment boundaries of the frontier gather — free from CSR."""
        cnts = (self.out_off[frontier + 1]
                - self.out_off[frontier]).astype(np.int64)
        bounds = np.zeros(frontier.size + 1, np.int64)
        np.cumsum(cnts, out=bounds[1:])
        return bounds

    def frontier_signatures_device(self, j: int, frontier: np.ndarray, *,
                                   dedup: bool = True):
        if not self._device:
            return None
        from .device_maint import frontier_fold
        p0, seg, lab, pid_tgt = self._gather_frontier(j, frontier)
        return frontier_fold(p0, seg, lab, pid_tgt, frontier.size,
                             dedup=dedup,
                             bounds=self._frontier_bounds(frontier),
                             cache=self._fold_cache, cache_key=frontier)

    def propagate_level_resident(self, j: int, frontier: np.ndarray, *,
                                 dedup: bool = True):
        """The fused per-level device program (fold + probe + mint +
        changed mask, one dispatch): only available with the store
        mirrored on device — with a host store the staged composition
        (`propagate_level_device`) is the device ceiling."""
        if not (self._device and self._dstores is not None):
            return None
        from .device_maint import resident_level_resolve
        p0, seg, lab, pid_tgt = self._gather_frontier(j, frontier)
        out, changed, n_changed, self.next_pid[j] = resident_level_resolve(
            self._dstores[j], p0, seg, lab, pid_tgt, frontier.size,
            self.pids[j][frontier], self.next_pid[j], dedup=dedup,
            bounds=self._frontier_bounds(frontier),
            cache=self._resident_cache, cache_key=frontier, level=j)
        return out, changed, n_changed

    def propagate_levels_resident(self, frontier: np.ndarray, *,
                                  dedup: bool = True):
        """The fused k-loop: one CSR gather feeds every level (the edge
        index set depends only on the frontier), one stacked upload, one
        dispatch, one scalar sync — see `resident_levels_resolve`."""
        if not (self._device and self._dstores is not None):
            return None
        from .device_maint import resident_levels_resolve
        k = len(self.pids) - 1
        if k == 0:
            return None
        idx, seg = _csr_gather(self.out_off, frontier)
        lab = self.graph.elabel[idx]
        dst = self.graph.dst[idx]
        nclean, dirty, next_pid_d = resident_levels_resolve(
            self._dstores[1:], self.pids[0][frontier], seg, lab,
            [self.pids[j - 1][dst] for j in range(1, k + 1)],
            frontier.size,
            [self.pids[j][frontier] for j in range(1, k + 1)],
            self.next_pid[1:], dedup=dedup,
            bounds=self._frontier_bounds(frontier),
            cache=self._resident_cache, cache_key=frontier)
        if dirty is not None:
            self.next_pid[nclean + 1] = next_pid_d
        return nclean, dirty


    def parents_of(self, nodes: np.ndarray) -> np.ndarray:
        idx, _ = _csr_gather(self.in_off, nodes)
        return np.unique(self.graph.src[self.in_ord[idx]]).astype(np.int64)

    def out_edges_of(self, nodes: np.ndarray):
        idx, _ = _csr_gather(self.out_off,
                             np.asarray(nodes, dtype=np.int64))
        g = self.graph
        return g.src[idx], g.elabel[idx], g.dst[idx]

    def node_labels_of(self, nodes: np.ndarray) -> np.ndarray:
        return self.graph.node_labels[np.asarray(nodes, dtype=np.int64)]

    def incident_edges(self, nid: int):
        g = self.graph
        mask = (g.src == nid) | (g.dst == nid)
        return g.src[mask], g.elabel[mask], g.dst[mask]

    # ------------------------------------------------------------ mutations
    def add_node_rows(self, labels: np.ndarray) -> int:
        base = self.graph.num_nodes
        self.graph = self.graph.with_nodes_added(labels)
        self._refresh_indexes()
        return base

    def add_edge_rows(self, src, elabel, dst) -> None:
        # Graph construction range-validates before this object is
        # committed, so a rejected insert leaves the backend untouched.
        self.graph = self.graph.with_edges_added(src, dst, elabel)
        self._refresh_indexes()

    def remove_edge_rows(self, src, elabel, dst) -> None:
        self.graph = self.graph.with_edges_removed(src, dst, elabel)
        self._refresh_indexes()

    def compact(self, keep: np.ndarray, remap: np.ndarray) -> None:
        g = self.graph
        # delete_node removed incident edges; keep only live-endpoint edges
        # anyway so a stale tombstone cannot corrupt the remap.
        emask = keep[g.src] & keep[g.dst]
        self.graph = Graph(
            g.node_labels[keep],
            remap[g.src[emask]].astype(np.int32),
            remap[g.dst[emask]].astype(np.int32),
            g.elabel[emask])  # monotone remap keeps (src,elabel,dst) order
        for j in range(len(self.pids)):
            self.pids[j] = self.pids[j][keep]
        self._refresh_indexes()

    # -------------------------------------------------------------- change k
    def truncate_k(self, new_k: int) -> None:
        self.pids = self.pids[: new_k + 1]
        if self._stores is not None:
            self._stores = self._stores[: new_k + 1]
        if self._dstores is not None:
            self._dstores = self._dstores[: new_k + 1]
        self.next_pid = self.next_pid[: new_k + 1]

    def extend_k(self, new_k: int, mode: str) -> None:
        # run additional iterations bottom-up from the stored pId_k,
        # through the same fused sig->rank program the build loop caches
        import jax.numpy as jnp
        cur_k = len(self.pids) - 1
        pid0 = jnp.asarray(self.pids[0].astype(np.int32))
        src = jnp.asarray(self.graph.src)
        dst = jnp.asarray(self.graph.dst)
        elab = jnp.asarray(self.graph.elabel)
        pid_prev = jnp.asarray(self.pids[cur_k].astype(np.int32))
        for j in range(cur_k + 1, new_k + 1):
            # pid_prev is donated (a buffer this loop owns); the host
            # copies below are taken before the next step consumes it
            _, pid_new, count, hi, lo = bisim_step(
                pid0, src, dst, elab, pid_prev,
                num_nodes=self.graph.num_nodes, mode=mode)
            pid_np = np.asarray(pid_new)
            store = SigStore.from_hash_pairs(
                np.asarray(hi), np.asarray(lo), pid_np)
            if self._dstores is not None:
                from .device_maint import DeviceSigStore
                self._dstores.append(DeviceSigStore(store))
            else:
                self._stores.append(store)
            self.next_pid.append(int(count))
            self.pids.append(pid_np.astype(np.int64))
            pid_prev = pid_new


class BisimMaintainer:
    """Holds a k-bisimulation partition and applies updates — the paper's
    update semantics over any `MaintenanceBackend`.

    Pass a `Graph` (wrapped in `InMemoryBackend`) or a ready backend such
    as `repro.exmem.maintenance.OocBackend`.

    ``device=True`` asks the backend for device-resident propagation
    (see the module docstring's contract); backends without the
    capability silently keep the host path, and `self.device` reports
    which one is active.  A transient device fault mid-stream
    (`TransientIOError`, what the fault layer injects) degrades to the
    bit-identical host path with a warning instead of aborting the
    stream — `self.device` flips to False and stays there.  Any other
    device error propagates.

    ``wal=True`` logs every logical update to the backend's write-ahead
    log *before* applying it (classic redo rule), so
    `snapshot()` + `BisimMaintainer.restore(...)` recover the maintained
    partition after a crash: the snapshot is the redo base and committed
    WAL records past its lsn are re-applied through these same methods.
    Requires a backend with `wal_supported` (OocBackend(wal=True)).
    """

    def __init__(self, graph, k: int, *, mode: str = "sorted",
                 rebuild_threshold: float = 0.5,
                 result: Optional[BisimResult] = None,
                 device: bool = False, wal: bool = False):
        if mode not in ("sorted", "dedup_hash", "multiset"):
            raise ValueError(f"unknown signature mode: {mode}")
        self.k = k
        self.mode = mode
        self.rebuild_threshold = rebuild_threshold
        self.backend = (graph if isinstance(graph, MaintenanceBackend)
                        else InMemoryBackend(graph))
        if wal and not self.backend.wal_supported:
            raise ValueError(
                "wal=True requires a backend with a write-ahead log "
                "(OocBackend(wal=True)); refusing to silently drop "
                "durability")
        self.wal = bool(wal)
        self._in_replay = False
        self._wal_depth = 0
        # delete_node leaves an isolated tombstone row (dense id space);
        # compact() later drops the flagged rows and remaps ids.
        self._tombstone = np.zeros(self.backend.num_nodes, dtype=bool)
        self.backend.build(k, mode, result=result)
        self.device = bool(device) and self.backend.enable_device()
        # per-level changed-node sets of the LAST update (index j = nodes
        # whose pId_j changed, 0..k); None = "assume everything changed"
        # (fresh build, §4.2 rebuild, compact, change_k).  The quotient
        # service reads this to patch touched blocks instead of
        # rematerializing.
        self.last_changed = None
        # optional scheduling hook: called as on_rebuild(level, frontier)
        # whenever the §4.2 heuristic fires mid-propagation, so a service
        # loop can account for the rebuild (e.g. force an early snapshot)
        self.on_rebuild = None

    # ------------------------------------------------------------ durability
    @contextlib.contextmanager
    def _logged(self, op: str, **arrays):
        """Write-ahead one logical update (redo rule: the record reaches
        the log *before* the mutation starts), then run it.  Nested ops
        (delete_node's inner delete_edges) and replayed ops are not
        re-logged — the WAL holds outermost logical updates only."""
        if not self.wal or self._in_replay or self._wal_depth:
            self._wal_depth += 1
            try:
                yield
            finally:
                self._wal_depth -= 1
            return
        self.backend.wal_append(op, arrays)
        self._wal_depth += 1
        try:
            yield
        finally:
            self._wal_depth -= 1

    @contextlib.contextmanager
    def already_logged(self):
        """Run update methods without re-logging them — for callers (the
        streaming service) that appended the records to the WAL at
        submit time, before the batch trigger fired."""
        self._wal_depth += 1
        try:
            yield
        finally:
            self._wal_depth -= 1

    def apply_ops(self, ops, *, logged: bool = True):
        """Apply a batch of mixed logical updates in order.

        ``ops`` is an iterable of ``(op_name, arrays)`` pairs in
        `_REPLAY_OPS` form (the WAL's record vocabulary).  Application
        order is exactly the given order — batching schedules *when*
        updates apply, never reorders them — so the pid history is
        bit-identical to applying each op individually, and therefore to
        a WAL replay of the same records.

        ``logged=False`` declares the records already WAL'd by the
        caller (submit-time append): nothing is re-logged, and ops the
        backend rejects (ValueError/OverflowError) are skipped and
        counted, mirroring what replay will do with the same record.
        ``logged=True`` logs each op normally and re-raises rejections.

        Returns ``(report, rejected)``: the merged `MaintenanceReport`
        (padded to k levels) and the rejected-op count.  After return,
        `last_changed` holds the per-level union of every applied op's
        changed sets (None if any op poisoned it: rebuild, compact with
        tombstones, change_k).
        """
        merged = MaintenanceReport([], [], [], device=self.device)
        union = [np.empty(0, dtype=np.int64) for _ in range(self.k + 1)]
        poisoned = False
        rejected = 0
        ctx = self.already_logged if not logged else contextlib.nullcontext
        with ctx():
            for op, arrays in ops:
                self.last_changed = None
                try:
                    out = self._REPLAY_OPS[op](self, arrays)
                except (ValueError, OverflowError):
                    if logged:
                        raise
                    rejected += 1
                    continue
                if isinstance(out, MaintenanceReport):
                    merged.merge(out)
                if poisoned:
                    continue
                if self.last_changed is None:
                    poisoned = True
                elif op == "change_k":
                    poisoned = True  # level count moved under the union
                else:
                    if len(self.last_changed) > len(union):
                        union.extend(np.empty(0, dtype=np.int64)
                                     for _ in range(len(self.last_changed)
                                                    - len(union)))
                    union = [np.union1d(u, c) for u, c in
                             zip(union, self.last_changed)]
        self.last_changed = None if poisoned else union
        return self._pad_report(merged), rejected

    def snapshot(self) -> None:
        """Persist the maintained partition durably: commit the WAL, then
        hand the backend everything the restore path needs beyond its own
        storage (k, mode, tombstones, whether the WAL is on).  After the
        snapshot commits, WAL records it absorbs are pruned."""
        if self.wal:
            self.backend.wal_flush()
        self.backend.snapshot(dict(
            k=int(self.k), mode=self.mode,
            rebuild_threshold=float(self.rebuild_threshold),
            wal=bool(self.wal),
            tombstone=np.asarray(self._tombstone, dtype=bool)))

    _REPLAY_OPS = {
        "add_nodes": lambda m, a: m.add_nodes(a["labels"]),
        "add_edges": lambda m, a: m.add_edges(a["src"], a["elabel"],
                                              a["dst"]),
        "delete_edges": lambda m, a: m.delete_edges(a["src"], a["elabel"],
                                                    a["dst"]),
        "delete_node": lambda m, a: m.delete_node(int(a["nid"][0])),
        "compact": lambda m, a: m.compact(),
        "change_k": lambda m, a: m.change_k(int(a["new_k"][0])),
    }

    @classmethod
    def restore(cls, backend: MaintenanceBackend, state: dict, *,
                device: bool = False) -> "BisimMaintainer":
        """Reconstruct a maintainer from a backend's restored snapshot
        (e.g. ``OocBackend.restore(workdir)``), then redo-replay every
        committed WAL record past the snapshot's lsn through the normal
        update methods.  The possibly half-mutated pre-crash live state
        is *not* consulted — recovery is snapshot + committed redo, so a
        crash mid-update can never leave a partially applied batch."""
        m = object.__new__(cls)
        m.k = int(state["k"])
        m.mode = state["mode"]
        m.rebuild_threshold = float(state["rebuild_threshold"])
        m.backend = backend
        m.wal = bool(state.get("wal", False)) and backend.wal_supported
        m._in_replay = False
        m._wal_depth = 0
        m._tombstone = np.asarray(state["tombstone"], dtype=bool)
        m.device = bool(device) and backend.enable_device()
        m.last_changed = None
        m.on_rebuild = None
        m._in_replay = True
        try:
            for _lsn, op, arrays in backend.wal_replay_records(
                    after_lsn=int(state.get("wal_lsn", 0))):
                try:
                    cls._REPLAY_OPS[op](m, arrays)
                except (ValueError, OverflowError):
                    # the record reaches the log before validation (redo
                    # rule), so an op the backend rejected is logged too;
                    # it left no state behind then and it raises the same
                    # way now — skip it, exactly as the caller did
                    pass
        finally:
            m._in_replay = False
        return m

    # ------------------------------------------------------------- queries
    @property
    def graph(self) -> Graph:
        """The maintained graph; out-of-core backends materialize a copy
        (tests / small graphs only)."""
        return self.backend.graph

    @property
    def pids(self) -> list:
        """Per-level pid columns; live arrays for the in-memory backend."""
        backend_pids = getattr(self.backend, "pids", None)
        if backend_pids is not None:
            return backend_pids
        return [self.backend.pid_column(j) for j in range(self.k + 1)]

    @property
    def stores(self) -> list:
        return self.backend.stores

    @property
    def next_pid(self) -> list:
        return self.backend.next_pid

    def pid(self, j: Optional[int] = None) -> np.ndarray:
        return self.backend.pid_column(self.k if j is None else j)

    def result(self) -> BisimResult:
        pids = [np.asarray(self.backend.pid_column(j), dtype=np.int64)
                for j in range(self.k + 1)]
        return BisimResult(
            pids=np.stack(pids),
            counts=[len(np.unique(p)) for p in pids], stats=[],
            converged_at=None, k_requested=self.k)

    # ------------------------------------------------------- ADD_NODE(S)
    def add_node(self, label: int) -> int:
        """Algorithm 2: add one isolated node."""
        return self.add_nodes([label])[0]

    def add_nodes(self, labels: Iterable[int]) -> list:
        """Algorithm 3: bulk insert isolated nodes (merge-join on labels)."""
        labels = np.asarray(list(labels), dtype=np.int32)
        with self._logged("add_nodes", labels=labels):
            base = self.backend.add_node_rows(labels)
            new_ids = list(range(base, base + labels.shape[0]))
            self._tombstone = np.concatenate(
                [self._tombstone, np.zeros(labels.shape[0], dtype=bool)])
            # level 0: one bulk resolve of the label keys (merge-join)
            p0 = self.backend.resolve(0, label_key(labels))
            self.backend.append_pid_rows(0, p0)
            # sig_j of an isolated node is (pId_0, {}) for every j >= 1:
            # the empty-set combine is the identity (0, 0), so its hash
            # only depends on p0 — one vectorized hash_triple per level.
            zero = np.zeros(labels.shape[0], np.uint32)
            hi, lo = hashes_np.hash_triple(zero, zero, p0)
            keys = fuse_key(hi, lo)
            for j in range(1, self.k + 1):
                self.backend.append_pid_rows(j,
                                             self.backend.resolve(j, keys))
            # every level gained pid rows for the new ids
            ids64 = np.asarray(new_ids, dtype=np.int64)
            self.last_changed = [ids64.copy() for _ in range(self.k + 1)]
        return new_ids

    # ------------------------------------------------------- ADD_EDGE(S)
    def add_edges(self, src, elabel, dst) -> MaintenanceReport:
        """Algorithm 4 (and its ADD_EDGES batch variant)."""
        src = np.atleast_1d(np.asarray(src, dtype=np.int32))
        dst = np.atleast_1d(np.asarray(dst, dtype=np.int32))
        elabel = np.atleast_1d(np.asarray(elabel, dtype=np.int32))
        with self._logged("add_edges", src=src, elabel=elabel, dst=dst):
            # the backend range-validates before mutating, so a rejected
            # insert must not re-animate anything
            with obs.span("maint.apply_edges", op="add", edges=src.size):
                self.backend.add_edge_rows(src, elabel, dst)
            # an edge incident to a tombstoned node re-animates it
            self._tombstone[src] = False
            self._tombstone[dst] = False
            return self._propagate(frontier0=np.unique(src))

    def add_edge(self, s: int, l: int, t: int) -> MaintenanceReport:
        return self.add_edges([s], [l], [t])

    def delete_edges(self, src, elabel, dst) -> MaintenanceReport:
        """Deletions (§4): same propagation pattern as insertion."""
        src = np.atleast_1d(np.asarray(src, dtype=np.int32))
        dst = np.atleast_1d(np.asarray(dst, dtype=np.int32))
        elabel = np.atleast_1d(np.asarray(elabel, dtype=np.int32))
        with self._logged("delete_edges", src=src, elabel=elabel, dst=dst):
            with obs.span("maint.apply_edges", op="delete",
                          edges=src.size):
                self.backend.remove_edge_rows(src, elabel, dst)
            return self._propagate(frontier0=np.unique(src))

    def delete_node(self, nid: int) -> MaintenanceReport:
        """Remove a node: first its incident edges, then the node row."""
        if not 0 <= nid < self.backend.num_nodes:
            # reject before any mutation (negative ids would wrap around
            # and tombstone a live row)
            raise ValueError(f"node id out of range: {nid}")
        with self._logged("delete_node",
                          nid=np.asarray([nid], dtype=np.int64)):
            src, elabel, dst = self.backend.incident_edges(nid)
            rep = self.delete_edges(src, elabel, dst)
            # The paper then drops the N_t row; we keep a tombstone
            # (isolated node) to preserve the dense id space until
            # compact() runs.
            self._tombstone[nid] = True
        return rep

    def compact(self) -> np.ndarray:
        """Drop tombstoned rows: densely remap node ids, slice the pid
        history, and rebuild the edge tables (the deferred half of the
        paper's DELETE_NODE, which removes the N_t row outright).

        Returns the old->new id map (int64 [old_N]; -1 for dropped rows).
        The stores are untouched: they map signatures, not node ids, and a
        surviving signature still denotes the same behavior class.
        """
        dead = self._tombstone
        remap = np.cumsum(~dead, dtype=np.int64) - 1
        remap[dead] = -1
        if not dead.any():
            empty = np.empty(0, dtype=np.int64)
            self.last_changed = [empty.copy() for _ in range(self.k + 1)]
            return remap
        with self._logged("compact"):
            self.backend.compact(~dead, remap)
            self._tombstone = np.zeros(self.backend.num_nodes, dtype=bool)
            self.last_changed = None  # node ids moved: everything changed
        return remap

    @property
    def num_tombstones(self) -> int:
        return int(self._tombstone.sum())

    # ------------------------------------------------------- propagation
    def _pad_report(self, report: MaintenanceReport) -> MaintenanceReport:
        """Pad the per-level lists to k entries (zeros) — the §4.2 rebuild
        returns mid-loop, and consumers index by level."""
        while len(report.nodes_checked) < self.k:
            report.nodes_checked.append(0)
            report.nodes_changed.append(0)
            report.partitions_touched.append(0)
            report.level_seconds.append(0.0)
        return report

    def _propagate(self, frontier0: np.ndarray) -> MaintenanceReport:
        with obs.span("maint.propagate", frontier=int(frontier0.size),
                      device=self.device):
            return self._propagate_inner(frontier0)

    def _propagate_inner(self, frontier0: np.ndarray) -> MaintenanceReport:
        n = self.backend.num_nodes
        report = MaintenanceReport([], [], [], device=self.device)
        # pId_0 never moves under edge updates; levels 1..k fill in below
        changed_levels = [np.empty(0, dtype=np.int64)]
        dedup = self.mode != "multiset"
        frontier = np.unique(frontier0).astype(np.int64)
        always = frontier.copy()  # (j, s) enqueued for every j (line 7-8)
        # fused k-loop prefix: ONE dispatch resolves every level while
        # nothing changes; the first change invalidates the later levels'
        # uploaded target pids and hands back to the per-level ladder
        nclean, dirty_commit, dt_fused = 0, None, 0.0
        if self.device and frontier.size \
                and frontier.size <= self.rebuild_threshold * n:
            t0 = time.perf_counter()
            multi = None
            try:
                fault_point("device", "level 1")
                multi = self.backend.propagate_levels_resident(
                    frontier, dedup=dedup)
            except TransientIOError as exc:
                warnings.warn(
                    f"device propagation failed ({exc!r}); degrading "
                    "to the bit-identical host path", RuntimeWarning)
                self.device = False
            if multi is not None:
                nclean, dirty_commit = multi
                # amortize the single dispatch over the levels it settled
                dt_fused = (time.perf_counter() - t0) / max(
                    nclean + (dirty_commit is not None), 1)
        fused_until = nclean + (dirty_commit is not None)
        for j in range(1, self.k + 1):
            t0 = time.perf_counter()
            if frontier.size == 0:
                report.nodes_checked.append(0)
                report.nodes_changed.append(0)
                report.partitions_touched.append(0)
                report.level_seconds.append(0.0)
                changed_levels.append(np.empty(0, dtype=np.int64))
                continue
            if frontier.size > self.rebuild_threshold * n:
                # §4.2 heuristic: most nodes queued -> full rebuild is cheaper
                with obs.span("maint.rebuild", level=j):
                    self.backend.build(self.k, self.mode)
                report.rebuilt = True
                self.last_changed = None  # rebuild re-ranks every level
                if self.on_rebuild is not None:
                    self.on_rebuild(j, int(frontier.size))
                return self._pad_report(report)
            with obs.span("maint.level", level=j,
                          frontier=int(frontier.size),
                          device=self.device) as lvl_sp:
                pj = None
                resident = None
                if j <= nclean:
                    # settled by the fused k-loop: confirmed unchanged
                    resident = (None, None, 0)
                elif j == nclean + 1 and dirty_commit is not None:
                    resident = dirty_commit
                    dirty_commit = None
                elif self.device:
                    try:
                        fault_point("device", f"level {j}")
                        # fallback ladder: device-fused (one dispatch,
                        # scalar sync) -> device-staged -> host
                        resident = self.backend.propagate_level_resident(
                            j, frontier, dedup=dedup)
                        if resident is None:
                            pj = self.backend.propagate_level_device(
                                j, frontier, dedup=dedup)
                    except TransientIOError as exc:
                        # graceful degradation from a transient fault (the
                        # fault layer's `fault_point("device", ...)`): the
                        # host path computes the bit-identical partition,
                        # so the stream is demoted instead of killed, for
                        # good (no retry storms).  Anything else — an XLA
                        # compile refusal, device OOM, a runtime error, a
                        # simulated crash — propagates: a device failure
                        # must never pass as a host-path run.
                        warnings.warn(
                            f"device propagation failed ({exc!r}); degrading "
                            "to the bit-identical host path", RuntimeWarning)
                        self.device = False
                        resident = None
                        pj = None
                if resident is not None:
                    # fused level: pid deltas crossed back only if
                    # something changed; the no-change steady state never
                    # touches the host pid columns
                    pj_full, changed_mask, n_changed = resident
                    if n_changed:
                        old = self.backend.pid_at(j, frontier)
                        self.backend.set_pid_at(j, frontier, pj_full)
                        changed = frontier[changed_mask]
                        touched = int(np.union1d(
                            old[changed_mask], pj_full[changed_mask]).size)
                    else:
                        changed = frontier[:0]
                        touched = 0
                    lvl_sp.set(changed=int(changed.size))
                    report.nodes_checked.append(int(frontier.size))
                    report.nodes_changed.append(int(changed.size))
                    report.partitions_touched.append(touched)
                else:
                    if pj is None:
                        hi, lo = self.backend.frontier_signatures(
                            j, frontier, dedup=dedup)
                        # one bulk resolve of the frontier against S_j
                        pj = self.backend.resolve(j, fuse_key(hi, lo))
                    old = self.backend.pid_at(j, frontier)
                    changed_mask = old != pj
                    self.backend.set_pid_at(j, frontier, pj)
                    changed = frontier[changed_mask]
                    lvl_sp.set(changed=int(changed.size))
                    report.nodes_checked.append(int(frontier.size))
                    report.nodes_changed.append(int(changed.size))
                    report.partitions_touched.append(
                        int(np.union1d(old[changed_mask],
                                       pj[changed_mask]).size))
                changed_levels.append(np.asarray(changed, dtype=np.int64))
                # propagate to parents of changed nodes (line 20; E_tts)
                if changed.size and j < self.k:
                    frontier = np.union1d(self.backend.parents_of(changed),
                                          always)
                else:
                    frontier = always.copy()
            report.level_seconds.append(
                time.perf_counter() - t0
                + (dt_fused if j <= fused_until else 0.0))
        self.last_changed = changed_levels
        return report

    # ---------------------------------------------------------- change k
    def change_k(self, new_k: int) -> None:
        """§4 'Change k': decrease slices history; increase runs extra
        iterations of Algorithm 1 on top of the stored state."""
        with self._logged("change_k",
                          new_k=np.asarray([new_k], dtype=np.int64)):
            if new_k <= self.k:
                self.backend.truncate_k(new_k)
            else:
                self.backend.extend_k(new_k, self.mode)
            self.k = new_k
            self.last_changed = None  # the level ladder itself moved
