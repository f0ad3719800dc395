"""Distributed Build_Bisim over a device mesh (shard_map).

Mapping of the paper's external-memory structure onto a TPU pod:

  * nodes are range-sharded across devices (device d owns a contiguous slice
    of node ids — the analogue of N_t pages resident on one disk);
  * edges are sharded **by owner of src** so that every node's out-edge
    segment is local to one device — the invariant the paper's sort order on
    E_t (by sId) provides, and what makes local dedup/segment-combine exact;
  * the sort-merge join E_t ⋈ N_t on tId (line 10 of Alg. 1) becomes an
    all-gather of the pid column followed by a local gather;
  * the signature store S becomes distributed dense ranking, with two
    implementations:
      - ranking='allgather' (baseline): all-gather all signature hashes,
        rank the full array on every device.  Collective bytes: 8·N per
        iteration per device; per-device compute O(N log N).
      - ranking='bucketed' (optimized): hash-bucketed all-to-all exchange,
        local ranking within buckets, global offsets from an 8·D-byte
        all-gather of bucket unique-counts, and an all-to-all route back.
        Collective bytes: ~16·N/D per device — a D-fold reduction, the
        distributed analogue of the paper replacing search(S) with
        sort-based bulk S.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.graph.storage import Graph
from . import signatures as sig
from .partition import BisimResult, IterationStats

from repro.compat import shard_map


@dataclasses.dataclass
class ShardedGraph:
    """Host-side padded + owner-sharded representation (built once)."""
    node_labels: np.ndarray  # int32 [N_pad]
    pid0: np.ndarray         # int32 [N_pad]
    src_local: np.ndarray    # int32 [D*e_loc]  (src - owner_base; 0 if invalid)
    dst: np.ndarray          # int32 [D*e_loc]  global target ids
    elabel: np.ndarray       # int32 [D*e_loc]
    valid: np.ndarray        # bool  [D*e_loc]
    num_nodes: int
    n_pad: int
    n_loc: int
    e_loc: int
    num_devices: int
    num_pid0: int

    @property
    def has_padding(self) -> bool:
        return self.n_pad > self.num_nodes


def shard_graph(graph: Graph, num_devices: int) -> ShardedGraph:
    """Partition the graph: owner-sharded edges, range-sharded nodes."""
    n = graph.num_nodes
    d = num_devices
    n_loc = -(-(n + 1) // d)  # >= 1 dummy node so padding always exists
    n_pad = n_loc * d

    sentinel = int(graph.node_labels.max()) + 1 if n else 0
    node_labels = np.full(n_pad, sentinel, dtype=np.int32)
    node_labels[:n] = graph.node_labels
    _, pid0 = np.unique(node_labels, return_inverse=True)
    pid0 = pid0.astype(np.int32)
    num_pid0 = int(pid0.max()) + 1 if n_pad else 0

    owner = graph.src // n_loc
    counts = np.bincount(owner, minlength=d)
    e_loc = max(int(counts.max()), 1)
    src_local = np.zeros((d, e_loc), dtype=np.int32)
    dst = np.zeros((d, e_loc), dtype=np.int32)
    elabel = np.zeros((d, e_loc), dtype=np.int32)
    valid = np.zeros((d, e_loc), dtype=bool)
    # edges are already sorted by src -> contiguous per owner
    starts = np.zeros(d + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for dev in range(d):
        lo, hi = starts[dev], starts[dev + 1]
        c = hi - lo
        src_local[dev, :c] = graph.src[lo:hi] - dev * n_loc
        dst[dev, :c] = graph.dst[lo:hi]
        elabel[dev, :c] = graph.elabel[lo:hi]
        valid[dev, :c] = True

    return ShardedGraph(
        node_labels=node_labels, pid0=pid0,
        src_local=src_local.reshape(-1), dst=dst.reshape(-1),
        elabel=elabel.reshape(-1), valid=valid.reshape(-1),
        num_nodes=n, n_pad=n_pad, n_loc=n_loc, e_loc=e_loc, num_devices=d,
        num_pid0=num_pid0)


# --------------------------------------------------------------------------
# per-device kernels (run inside shard_map)
# --------------------------------------------------------------------------

def _local_signatures(pid_prev_full, pid0_loc, src_local, dst, elabel, valid,
                      n_loc: int, mode: str):
    """Local signature hashes for the n_loc owned nodes."""
    pid_tgt = pid_prev_full[dst]
    if mode == "multiset":
        e_hi, e_lo = sig.hash_pair(elabel, pid_tgt)
        e_hi = jnp.where(valid, e_hi, jnp.uint32(0))
        e_lo = jnp.where(valid, e_lo, jnp.uint32(0))
        seg = jnp.where(valid, src_local, 0)
    else:
        if mode == "sorted":  # paper-faithful 3-key sort of the triple
            key_src = jnp.where(valid, src_local, n_loc)  # invalid last
            order = jnp.lexsort((pid_tgt, elabel, key_src))
            s_src = key_src[order]
            s_a, s_b = elabel[order], pid_tgt[order]
            dup = jnp.concatenate([
                jnp.zeros((1,), bool),
                (s_src[1:] == s_src[:-1]) & (s_a[1:] == s_a[:-1])
                & (s_b[1:] == s_b[:-1])])
            e_hi, e_lo = sig.hash_pair(s_a, s_b)
        else:  # dedup_hash: single fused-hash key sort
            e_hi0, e_lo0 = sig.hash_pair(elabel, pid_tgt)
            key_src = jnp.where(valid, src_local, n_loc)
            order = jnp.lexsort((e_lo0, e_hi0, key_src))
            s_src = key_src[order]
            e_hi, e_lo = e_hi0[order], e_lo0[order]
            dup = jnp.concatenate([
                jnp.zeros((1,), bool),
                (s_src[1:] == s_src[:-1]) & (e_hi[1:] == e_hi[:-1])
                & (e_lo[1:] == e_lo[:-1])])
        keep = (~dup) & (s_src < n_loc)
        e_hi = jnp.where(keep, e_hi, jnp.uint32(0))
        e_lo = jnp.where(keep, e_lo, jnp.uint32(0))
        seg = jnp.where(s_src < n_loc, s_src, 0)
    seg_hi = jax.ops.segment_sum(e_hi, seg, num_segments=n_loc)
    seg_lo = jax.ops.segment_sum(e_lo, seg, num_segments=n_loc)
    return sig.hash_triple(seg_hi, seg_lo, pid0_loc)


def _rank_allgather(sig_hi, sig_lo, axis, n_loc):
    all_hi = jax.lax.all_gather(sig_hi, axis, tiled=True)
    all_lo = jax.lax.all_gather(sig_lo, axis, tiled=True)
    pid_full, count = sig.dense_rank_pairs(all_hi, all_lo)
    idx = jax.lax.axis_index(axis)
    pid_loc = jax.lax.dynamic_slice_in_dim(pid_full, idx * n_loc, n_loc)
    return pid_loc, count, jnp.int32(0)


def _rank_bucketed(sig_hi, sig_lo, axis, n_loc, num_devices, capacity):
    """Distributed dense ranking via hash-bucketed all-to-all."""
    d = num_devices
    bucket = (sig_hi % jnp.uint32(d)).astype(jnp.int32)
    order = jnp.argsort(bucket)
    sb = bucket[order]
    shi, slo = sig_hi[order], sig_lo[order]
    # position of each element within its bucket
    start = jnp.searchsorted(sb, jnp.arange(d, dtype=sb.dtype))
    pos = jnp.arange(n_loc, dtype=jnp.int32) - start[sb].astype(jnp.int32)
    overflow = (pos >= capacity).sum().astype(jnp.int32)
    send_hi = jnp.zeros((d, capacity), jnp.uint32).at[sb, pos].set(
        shi, mode="drop")
    send_lo = jnp.zeros((d, capacity), jnp.uint32).at[sb, pos].set(
        slo, mode="drop")
    send_ok = jnp.zeros((d, capacity), bool).at[sb, pos].set(
        True, mode="drop")
    recv_hi = jax.lax.all_to_all(send_hi, axis, 0, 0, tiled=False)
    recv_lo = jax.lax.all_to_all(send_lo, axis, 0, 0, tiled=False)
    recv_ok = jax.lax.all_to_all(send_ok, axis, 0, 0, tiled=False)
    fhi = recv_hi.reshape(-1)
    flo = recv_lo.reshape(-1)
    fok = recv_ok.reshape(-1)
    # rank valid elements locally (invalid sort last via the ~valid key)
    r_order = jnp.lexsort((flo, fhi, ~fok))
    r_hi, r_lo, r_ok = fhi[r_order], flo[r_order], fok[r_order]
    first = jnp.concatenate([
        jnp.ones((1,), bool),
        (r_hi[1:] != r_hi[:-1]) | (r_lo[1:] != r_lo[:-1])])
    new = first & r_ok
    local_rank = (jnp.cumsum(new) - 1).astype(jnp.int32)
    uniques = new.sum().astype(jnp.int32)
    # global offset for this device's bucket
    all_uniques = jax.lax.all_gather(uniques, axis)          # [D]
    idx = jax.lax.axis_index(axis)
    offset = jnp.where(jnp.arange(d) < idx, all_uniques, 0).sum().astype(
        jnp.int32)
    granks_sorted = jnp.where(r_ok, offset + local_rank, 0)
    granks = jnp.zeros((d * capacity,), jnp.int32).at[r_order].set(
        granks_sorted)
    # route ranks back: all_to_all restores (origin, slot) layout
    back = jax.lax.all_to_all(granks.reshape(d, capacity), axis, 0, 0)
    pid_sorted = back[sb, jnp.minimum(pos, capacity - 1)]
    pid_loc = jnp.zeros((n_loc,), jnp.int32).at[order].set(pid_sorted)
    count = jax.lax.psum(uniques, axis)
    overflow = jax.lax.psum(overflow, axis)
    return pid_loc, count, overflow


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "n_loc", "mode", "ranking", "capacity"))
def _distributed_step(pid_prev, pid0, src_local, dst, elabel, valid, *,
                      mesh, axis, n_loc, mode, ranking, capacity):
    d = int(np.prod([mesh.shape[a] for a in axis]))

    def step(pid_prev_loc, pid0_loc, src_loc, dst_loc, elab_loc, valid_loc):
        pid_full = jax.lax.all_gather(pid_prev_loc, axis, tiled=True)
        sig_hi, sig_lo = _local_signatures(
            pid_full, pid0_loc, src_loc, dst_loc, elab_loc, valid_loc,
            n_loc, mode)
        if ranking == "allgather":
            return _rank_allgather(sig_hi, sig_lo, axis, n_loc)
        return _rank_bucketed(sig_hi, sig_lo, axis, n_loc, d, capacity)

    spec = P(axis)
    return shard_map(
        step, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, spec),
        out_specs=(spec, P(), P()),
        check_vma=False,  # count/overflow are replicated by construction
    )(pid_prev, pid0, src_local, dst, elabel, valid)


def make_flat_mesh(devices=None):
    devices = devices if devices is not None else jax.devices()
    return jax.make_mesh((len(devices),), ("devices",), devices=devices)


def place_sharded(sg: ShardedGraph, mesh, axis=("devices",)) -> dict:
    """The build's device inputs: every per-node and per-edge column of
    `sg`, split along `axis` so that device d holds its own slice."""
    sharding = jax.sharding.NamedSharding(mesh, P(axis))
    return {name: jax.device_put(jnp.asarray(getattr(sg, name)), sharding)
            for name in ("pid0", "src_local", "dst", "elabel", "valid")}


def build_bisim_distributed(
        graph: Graph, k: int, *, mesh=None, axis=("devices",),
        mode: str = "sorted", ranking: str = "allgather",
        early_stop: bool = True, capacity_factor: float = 4.0,
        sharded: Optional[ShardedGraph] = None) -> BisimResult:
    """Multi-device Build_Bisim.  Semantics identical to build_bisim()."""
    import time as _time
    if mesh is None:
        mesh = make_flat_mesh()
    if isinstance(axis, str):
        axis = (axis,)
    d = int(np.prod([mesh.shape[a] for a in axis]))
    sg = sharded if sharded is not None else shard_graph(graph, d)
    n, n_loc = sg.num_nodes, sg.n_loc
    # One sender can route at most n_loc items to a single bucket, so
    # capacity=n_loc is always safe; the probabilistic bound (Chernoff on
    # hash balance) only pays off for large shards.
    if n_loc <= 4096:
        capacity = n_loc
    else:
        capacity = max(int(np.ceil(n_loc / d * capacity_factor)), 8)

    pid0, src_local, dst, elabel, valid = place_sharded(
        sg, mesh, axis).values()

    pad_parts = 1 if sg.has_padding else 0
    counts = [sg.num_pid0 - pad_parts]
    history = [sg.pid0[:n].copy()]
    stats = [IterationStats(0, counts[0], 0.0, 4 * n, 4 * n)]
    pid_prev = pid0
    converged_at = None
    for j in range(1, k + 1):
        t0 = _time.perf_counter()
        pid_new, count, overflow = _distributed_step(
            pid_prev, pid0, src_local, dst, elabel, valid, mesh=mesh,
            axis=axis, n_loc=n_loc, mode=mode, ranking=ranking,
            capacity=capacity)
        pid_new.block_until_ready()
        if int(overflow) > 0:
            raise RuntimeError(
                f"bucketed ranking overflow ({int(overflow)} elements); "
                f"increase capacity_factor (> {capacity_factor})")
        dt = _time.perf_counter() - t0
        c = int(count) - pad_parts
        counts.append(c)
        history.append(np.asarray(pid_new)[:n])
        stats.append(IterationStats(j, c, dt, 12 * sg.e_loc * d, 8 * sg.n_pad))
        if early_stop and counts[-1] == counts[-2]:
            converged_at = j
            break
        pid_prev = pid_new

    return BisimResult(pids=np.stack(history), counts=counts, stats=stats,
                       converged_at=converged_at, k_requested=k)
