"""Signature construction for k-bisimulation (Definition 3 of the paper).

The paper materializes `sig_k(u) = (pId_0(u), {(eLabel, pId_{k-1}(tgt))})` as
a sorted string and maps it to a partition id through the store S. Strings
are hostile to fixed-shape SIMD hardware, so the TPU-native adaptation
represents every signature as a pair of independent 32-bit mix-hashes
(an effective 64-bit identifier; 64-bit integers are avoided because TPU
vector units are 32-bit). `S.insert` becomes dense ranking of these hash
pairs — exactly the paper's own sort-based bulk implementation of S (§3.2).

Three signature modes, all O(scan/sort) in the paper's sense:

  * ``sorted``   — paper-faithful: lexsort edge triples (src, eLabel, pid),
                   mask duplicates (set semantics), segment-combine.
                   One 3-key sort of E per iteration = the paper's
                   `O(sort(|E_t|))` term.
  * ``dedup_hash`` — beyond-paper: sort a single fused 64-bit per-edge hash
                   per source segment instead of the 3-key triple; dedup on
                   the hash; exact set semantics w.h.p., ~1/3 the sort keys.
  * ``multiset`` — beyond-paper, sort-free: order-independent segment-sum of
                   per-edge hashes. Computes *counting* bisimulation (a
                   refinement of k-bisimulation; identical when no node has
                   two out-edges with equal (eLabel, pid) at some level).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

U32 = jnp.uint32

# xxhash/murmur-style odd constants.
_C1 = jnp.uint32(0x9E3779B1)
_C2 = jnp.uint32(0x85EBCA77)
_C3 = jnp.uint32(0xC2B2AE3D)
_C4 = jnp.uint32(0x27D4EB2F)
_C5 = jnp.uint32(0x165667B1)
_SEED_LO = jnp.uint32(0x2545F491)
_SEED_HI = jnp.uint32(0x9E3779B9)


def fmix32(h: jax.Array) -> jax.Array:
    """murmur3 32-bit finalizer (bijective avalanche mix)."""
    h = h.astype(U32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def hash_pair(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """64-bit (as two u32 lanes) hash of an integer pair."""
    a = a.astype(U32)
    b = b.astype(U32)
    lo = fmix32(a * _C1 + b * _C2 + _SEED_LO)
    hi = fmix32(a * _C3 + b * _C4 + _SEED_HI)
    # cross-mix the lanes so (hi, lo) are not independent of lane swaps
    return fmix32(hi + lo * _C5), lo


def hash_triple(a, b, c) -> tuple[jax.Array, jax.Array]:
    h1, l1 = hash_pair(a, b)
    return hash_pair(h1 + c.astype(U32) * _C5, l1 ^ c.astype(U32))


def dense_rank_pairs(hi: jax.Array, lo: jax.Array):
    """Dense-rank (hi, lo) hash pairs: equal pair -> equal rank in [0, P).

    This is the sort-based implementation of the signature store S: sort all
    signatures, assign ids while scanning (paper §3.2, "we could sort all
    signatures from F in an I/O efficient way ... partition identifiers are
    assigned [while scanning]").

    Returns (rank[int32 n], num_partitions[int32]).
    """
    order = jnp.lexsort((lo, hi))
    shi, slo = hi[order], lo[order]
    new = jnp.concatenate([
        jnp.ones((1,), dtype=bool),
        (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1]),
    ])
    ranks = (jnp.cumsum(new) - 1).astype(jnp.int32)
    pid = jnp.zeros_like(ranks).at[order].set(ranks)
    return pid, new.sum().astype(jnp.int32)


def dense_rank_ints(x: jax.Array):
    """Dense-rank plain integers (used for pId_0 from node labels)."""
    order = jnp.argsort(x)
    sx = x[order]
    new = jnp.concatenate([jnp.ones((1,), bool), sx[1:] != sx[:-1]])
    ranks = (jnp.cumsum(new) - 1).astype(jnp.int32)
    pid = jnp.zeros_like(ranks).at[order].set(ranks)
    return pid, new.sum().astype(jnp.int32)


def segment_wrapsum(vals: jax.Array, bounds: jax.Array) -> jax.Array:
    """Per-segment wrap-add (mod 2^32) of contiguous segments.

    ``bounds`` [S+1] are the segment boundaries into `vals` (segment s is
    vals[bounds[s]:bounds[s+1]]).  Contiguity turns the segmented sum
    into one cumulative sum plus two boundary gathers — no scatter, which
    XLA CPU executes row by row.  Wrap subtraction of the running u32
    sums gives exactly the segment's wrap-add total, so this is
    bit-identical to `jax.ops.segment_sum` on u32 lanes.
    """
    cs = jnp.cumsum(vals, dtype=vals.dtype)
    starts = bounds[:-1]
    ends = bounds[1:]
    upper = cs[jnp.maximum(ends - 1, 0)]
    lower = jnp.where(starts > 0, cs[jnp.maximum(starts - 1, 0)],
                      jnp.zeros((), vals.dtype))
    return jnp.where(ends > starts, upper - lower,
                     jnp.zeros((), vals.dtype))


@functools.partial(jax.jit, static_argnames=("num_sigs",))
def frontier_signature_hashes_presorted(pid0: jax.Array, elabel: jax.Array,
                                        pid_tgt: jax.Array,
                                        bounds: jax.Array, count, *,
                                        num_sigs: int):
    """Segless frontier fold: hash + contiguous segment wrap-sum + final
    mix, for edge batches already grouped by frontier position (`bounds`)
    and — when set semantics apply — already deduplicated.  This is the
    common device program of the maintenance fold: the plain multiset
    path and the host-sorted dedup path both land here (see
    `device_maint.frontier_fold`).  Entries past `count` are padding.
    """
    valid = jnp.arange(elabel.shape[0], dtype=jnp.int32) < count
    zero = jnp.uint32(0)
    e_hi, e_lo = hash_pair(elabel, pid_tgt)
    e_hi = jnp.where(valid, e_hi, zero)
    e_lo = jnp.where(valid, e_lo, zero)
    return hash_triple(segment_wrapsum(e_hi, bounds),
                       segment_wrapsum(e_lo, bounds), pid0)


@functools.partial(jax.jit,
                   static_argnames=("num_sigs", "dedup", "use_kernel"))
def frontier_signature_hashes(pid0: jax.Array, seg: jax.Array,
                              elabel: jax.Array, pid_tgt: jax.Array,
                              bounds: jax.Array, count, *, num_sigs: int,
                              dedup: bool = True, use_kernel: bool = False):
    """Device analogue of `hashes_np.signatures_from_edges` (maintenance §4).

    The maintenance frontier gather hands over flat (seg, eLabel,
    pId_{j-1}(tgt)) columns — seg[i] is the frontier position edge i
    belongs to, and seg must be *ascending* (frontiers are sorted and the
    gathers emit edges in frontier order) with `bounds` [num_sigs+1] its
    segment boundaries — padded to a fixed shape (entries past `count`;
    padded seg entries must be >= num_sigs so they sort last and fall out
    of the segment sum).  Bit-identical to the numpy path: same dedup
    rule (one survivor per (seg, eLabel, pId) triple), same wrap-add
    combine, same mix-hash lanes — asserted by tests.

    pid0    u32 [num_sigs]  pId_0 of each frontier node
    Returns (hi, lo) u32 [num_sigs]; slots past the true frontier length
    hold garbage the caller trims.
    """
    if dedup:
        # the numpy path's np.lexsort((tgt, lab, seg)): primary seg, then
        # label, then pid — equal triples land contiguous either way, so
        # signed-vs-unsigned comparison differences cannot change the
        # mask.  seg's multiset is unchanged by the sort, so `bounds`
        # still delimits the segments afterwards.
        order = jnp.lexsort((pid_tgt, elabel, seg))
        sseg = seg[order]
        slab = elabel[order]
        stgt = pid_tgt[order]
        sval = order < count  # padding sits past `count` in probe order
        if use_kernel:
            # set semantics on TPU: device lexsort (above) + the Pallas
            # fold's in-kernel adjacent-compare dedup (presorted lanes)
            from repro.kernels import sig_fold as kernel_fold
            seg_hi, seg_lo = kernel_fold.frontier_sig_fold(
                slab, stgt, sseg, sval, num_sigs=num_sigs, dedup=True)
            return hash_triple(seg_hi, seg_lo, pid0)
        keep = jnp.concatenate([
            jnp.ones((1,), bool),
            (sseg[1:] != sseg[:-1]) | (slab[1:] != slab[:-1])
            | (stgt[1:] != stgt[:-1]),
        ]) & sval
        zero = jnp.uint32(0)
        e_hi, e_lo = hash_pair(slab, stgt)
        e_hi = jnp.where(keep, e_hi, zero)
        e_lo = jnp.where(keep, e_lo, zero)
        return hash_triple(segment_wrapsum(e_hi, bounds),
                           segment_wrapsum(e_lo, bounds), pid0)
    if use_kernel:
        # multiset mode on TPU: the whole fold is the Pallas kernel's
        # masked hash + segmented sum
        from repro.kernels import sig_fold as kernel_fold
        valid = jnp.arange(elabel.shape[0], dtype=jnp.int32) < count
        seg_hi, seg_lo = kernel_fold.frontier_sig_fold(
            elabel, pid_tgt, seg, valid, num_sigs=num_sigs)
        return hash_triple(seg_hi, seg_lo, pid0)
    return frontier_signature_hashes_presorted(
        pid0, elabel, pid_tgt, bounds, count, num_sigs=num_sigs)


@functools.partial(jax.jit, static_argnames=("num_nodes", "mode", "use_kernel"))
def signature_hashes(pid0: jax.Array, src: jax.Array, dst: jax.Array,
                     elabel: jax.Array, pid_prev: jax.Array, *,
                     num_nodes: int, mode: str = "sorted",
                     use_kernel: bool = False):
    """Compute sig_j hash pairs for every node.

    pid0      int32 [N]  iteration-0 partition ids
    src/dst/elabel int32 [E]  edge columns (any order; `sorted` mode sorts)
    pid_prev  int32 [N]  iteration j-1 partition ids

    Returns (sig_hi, sig_lo) u32 [N].
    """
    pid_tgt = pid_prev[dst]  # the sort-merge join E_t ⋈ N_t (line 10, Alg. 1)

    if mode == "sorted":
        # Paper-faithful: sort F = (sId, eLabel, pId_old_tId), remove dups
        # (lines 12-13 of Algorithm 1), then combine per source segment.
        order = jnp.lexsort((pid_tgt, elabel, src))
        s_src = src[order]
        s_lab = elabel[order]
        s_pid = pid_tgt[order]
        dup = jnp.concatenate([
            jnp.zeros((1,), bool),
            (s_src[1:] == s_src[:-1]) & (s_lab[1:] == s_lab[:-1])
            & (s_pid[1:] == s_pid[:-1]),
        ])
        e_hi, e_lo = hash_pair(s_lab, s_pid)
        e_hi = jnp.where(dup, jnp.uint32(0), e_hi)
        e_lo = jnp.where(dup, jnp.uint32(0), e_lo)
        seg = s_src
    elif mode == "dedup_hash":
        # Sort the fused 64-bit edge hash within source segments; dedup on it.
        e_hi, e_lo = hash_pair(elabel, pid_tgt)
        order = jnp.lexsort((e_lo, e_hi, src))
        s_src = src[order]
        s_hi = e_hi[order]
        s_lo = e_lo[order]
        dup = jnp.concatenate([
            jnp.zeros((1,), bool),
            (s_src[1:] == s_src[:-1]) & (s_hi[1:] == s_hi[:-1])
            & (s_lo[1:] == s_lo[:-1]),
        ])
        e_hi = jnp.where(dup, jnp.uint32(0), s_hi)
        e_lo = jnp.where(dup, jnp.uint32(0), s_lo)
        seg = s_src
    elif mode == "multiset":
        # Sort-free: order-independent multiset hash (counting bisimulation).
        if use_kernel:
            from repro.kernels import ops as kernel_ops
            e_hi, e_lo = kernel_ops.edge_hash(elabel, pid_tgt)
        else:
            e_hi, e_lo = hash_pair(elabel, pid_tgt)
        seg = src
    else:
        raise ValueError(f"unknown signature mode: {mode}")

    # Order-independent combine per source (sum mod 2^32 in each lane). After
    # dedup this is an exact set hash; empty segments get the identity (0,0).
    seg_hi = jax.ops.segment_sum(e_hi, seg, num_segments=num_nodes)
    seg_lo = jax.ops.segment_sum(e_lo, seg, num_segments=num_nodes)
    return hash_triple(seg_hi, seg_lo, pid0)
