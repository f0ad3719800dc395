"""Pallas TPU kernels for the paper's hot loop: signature construction.

Algorithm 1 line 14-15 streams F = (sId, eLabel, pId_old_tId) and folds each
source's (eLabel, pId) pairs into its signature. On TPU the fold becomes:
per-edge 2x32-bit mix-hash + masked segmented sum — a memory-bound fused op.

All kernel arithmetic is int32: the hash lanes are the u32 mix-hash of
`repro.core.signatures` computed bit-for-bit in two's complement (logical
shifts are masked arithmetic shifts, wrap-around multiply and add are the
same bits), masks are int32 refs, and the wrappers bitcast the results
back to uint32.  The v5e lowering has no unsigned reductions and no bool
refs, and its vector tiles are (8, 128) — every block below is a whole
number of those tiles or the full array.

Two layouts:

  * `sig_fold` — *blocked CSR*: the edge stream is partitioned so that
    block i only contains edges whose source lies in node-block i
    (`nodes_per_block` nodes, built once by `ops.blocked_csr_layout`;
    skewed blocks are padded).  Eight node-blocks form one (8, eb) tile
    per grid step, and each node's sum is a compare + lane reduction
    over its block row.  Multiset semantics.

  * `frontier_sig_fold` / `chunk_sig_fold` — *ascending segments*: the
    maintenance frontier batch and the oocore sorted-run chunk both
    arrive with ascending segment ids, optionally deduplicated in-kernel
    by an adjacent compare (set semantics on (seg, eLabel, pId)-sorted
    lanes).  The lanes are tiled (rows, 128); a log-step segmented
    inclusive scan built from lane/sublane rolls sums each segment, a
    carry in SMEM scratch continues the last segment across grid steps,
    and the wrapper scatters each segment's last-lane total to its id.
    VMEM per step is bounded by the tile, whatever the batch length.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode


def _i32c(c: int) -> np.int32:
    """A u32 constant's bits as an int32 literal (numpy scalars stay
    jaxpr literals: no captured-constant closures in Pallas)."""
    return np.array(c, np.uint32).view(np.int32)


_C1 = _i32c(0x9E3779B1)
_C2 = _i32c(0x85EBCA77)
_C3 = _i32c(0xC2B2AE3D)
_C4 = _i32c(0x27D4EB2F)
_C5 = _i32c(0x165667B1)
_M1 = _i32c(0x85EBCA6B)
_M2 = _i32c(0xC2B2AE35)
_SEED_LO = _i32c(0x2545F491)
_SEED_HI = _i32c(0x9E3779B9)

_LANES = 128
_MAX_ROWS = 512          # ascending-segment tile: 512 x 128 lanes per step
_SEG_PAD = np.iinfo(np.int32).max


def _srl(h, n: int):
    """Logical right shift of int32 lanes (arithmetic shift + mask)."""
    return (h >> n) & np.int32((1 << (32 - n)) - 1)


def _fmix32(h):
    h = h ^ _srl(h, 16)
    h = h * _M1
    h = h ^ _srl(h, 13)
    h = h * _M2
    h = h ^ _srl(h, 16)
    return h


def _edge_hash(a, b):
    """Per-edge hash lanes (VPU, fused with the loads); the int32 image
    of `signatures.hash_pair`."""
    lo = _fmix32(a * _C1 + b * _C2 + _SEED_LO)
    hi = _fmix32(a * _C3 + b * _C4 + _SEED_HI)
    return _fmix32(hi + lo * _C5), lo


def _as_i32(x):
    """Bit-preserving int32 view of an integer or bool column."""
    x = jnp.asarray(x)
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(jnp.int32)


def _as_u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


# ------------------------------------------------------------ blocked CSR
def _blocked_kernel(elabel_ref, pid_ref, lsrc_ref, valid_ref, hi_ref, lo_ref,
                    *, nodes_per_block: int):
    hi, lo = _edge_hash(elabel_ref[...], pid_ref[...])
    keep = valid_ref[...] != 0
    hi = jnp.where(keep, hi, 0)
    lo = jnp.where(keep, lo, 0)
    lsrc = lsrc_ref[...]
    rows = hi.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, nodes_per_block), 1)
    out_hi = jnp.zeros((rows, nodes_per_block), jnp.int32)
    out_lo = jnp.zeros((rows, nodes_per_block), jnp.int32)
    # segmented sum within each node block: compare + lane reduction per
    # node (wrap-add in int32 is the u32 wrap-add bit for bit)
    for n in range(nodes_per_block):
        sel = lsrc == n
        s_hi = jnp.sum(jnp.where(sel, hi, 0), axis=1, keepdims=True)
        s_lo = jnp.sum(jnp.where(sel, lo, 0), axis=1, keepdims=True)
        out_hi = jnp.where(col == n, s_hi, out_hi)
        out_lo = jnp.where(col == n, s_lo, out_lo)
    hi_ref[...] = out_hi
    lo_ref[...] = out_lo


@functools.partial(
    jax.jit,
    static_argnames=("nodes_per_block", "edges_per_block", "interpret"))
def sig_fold(elabel, pid_tgt, local_src, valid, *, nodes_per_block: int,
             edges_per_block: int, interpret: Optional[bool] = None):
    """Blocked-CSR segmented signature fold (multiset semantics).

    elabel/pid_tgt/local_src: int32 [num_blocks * edges_per_block]
    valid: bool  (same shape); local_src is src minus the block's node base.
    Returns (seg_hi, seg_lo): uint32 [num_blocks * nodes_per_block].
    """
    e = elabel.shape[0]
    eb, nb = edges_per_block, nodes_per_block
    assert e % eb == 0
    num_blocks = e // eb
    rows = -(-num_blocks // 8) * 8   # whole (8, eb) tiles of node blocks

    def tile(x):
        x = _as_i32(x).reshape(num_blocks, eb)
        return jnp.pad(x, ((0, rows - num_blocks), (0, 0)))

    in_spec = pl.BlockSpec((8, eb), lambda i: (i, 0))
    out_spec = pl.BlockSpec((8, nb), lambda i: (i, 0))
    hi, lo = pl.pallas_call(
        functools.partial(_blocked_kernel, nodes_per_block=nb),
        grid=(rows // 8,),
        in_specs=[in_spec] * 4,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, nb), jnp.int32)] * 2,
        interpret=interpret_mode(interpret),
    )(tile(elabel), tile(pid_tgt), tile(local_src), tile(valid))
    n_out = num_blocks * nb
    return (_as_u32(hi.reshape(-1)[:n_out]),
            _as_u32(lo.reshape(-1)[:n_out]))


# ----------------------------------------------------- ascending segments
def _shift(x, d: int, col):
    """x shifted forward by `d` flat lanes of the (rows, 128) tile:
    out[p] = x[p - d] for p >= d (lanes p < d hold wrapped garbage the
    caller masks)."""
    rows = x.shape[0]
    q, r = divmod(d, _LANES)
    if r == 0:
        return pltpu.roll(x, q % rows, 0)
    lane = pltpu.roll(x, r, 1)          # lane[i, c] = x[i, c - r mod 128]
    same = pltpu.roll(lane, q % rows, 0) if q % rows else lane
    prev = pltpu.roll(lane, (q + 1) % rows, 0)
    return jnp.where(col >= r, same, prev)


def _seg_scan_kernel(keep0_ref, a_ref, b_ref, seg_ref, valid_ref,
                     hi_ref, lo_ref, carry, *, dedup: bool):
    step = pl.program_id(0)
    a = a_ref[...]
    b = b_ref[...]
    seg = seg_ref[...]
    rows = a.shape[0]
    shape = (rows, _LANES)

    # carry (SMEM): the previous step's last lane — its segment id, the
    # segment's running (hi, lo) sums and its (eLabel, pId) for dedup
    @pl.when(step == 0)
    def _init():
        carry[0] = jnp.int32(-1)   # matches no segment
        for i in range(1, 5):
            carry[i] = jnp.int32(0)

    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    flat = row * _LANES + col
    keep = valid_ref[...] != 0
    if dedup:
        # set semantics on (seg, eLabel, pId)-sorted lanes: a lane survives
        # iff it differs from its predecessor; the tile's first lane
        # compares with the carried lane, and the stream's very first
        # lane takes the caller's boundary bit instead
        first = flat == 0
        ps = jnp.where(first, carry[0], _shift(seg, 1, col))
        pa = jnp.where(first, carry[3], _shift(a, 1, col))
        pb = jnp.where(first, carry[4], _shift(b, 1, col))
        differs = (seg != ps) | (a != pa) | (b != pb)
        lead = jnp.where(step == 0, keep0_ref[0], jnp.int32(1))
        keep = keep & differs & (jnp.where(first, lead, jnp.int32(1)) != 0)
    hi, lo = _edge_hash(a, b)
    hi = jnp.where(keep, hi, 0)
    lo = jnp.where(keep, lo, 0)
    # segmented inclusive scan (Hillis-Steele): segments are contiguous,
    # so lane p - d belongs to p's segment iff their ids are equal
    d = 1
    while d < rows * _LANES:
        same = (flat >= d) & (_shift(seg, d, col) == seg)
        hi = hi + jnp.where(same, _shift(hi, d, col), 0)
        lo = lo + jnp.where(same, _shift(lo, d, col), 0)
        d *= 2
    # the previous step's last segment continues into this tile's first
    cont = seg == carry[0]
    hi = hi + jnp.where(cont, carry[1], 0)
    lo = lo + jnp.where(cont, carry[2], 0)
    hi_ref[...] = hi
    lo_ref[...] = lo

    is_last = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) \
        == _LANES - 1

    def last(x):
        return jnp.sum(jnp.where(is_last, x[rows - 1:rows, :], 0))

    carry[0] = last(seg)
    carry[1] = last(hi)
    carry[2] = last(lo)
    carry[3] = last(a)
    carry[4] = last(b)


def _seg_fold(elabel, pid_tgt, seg, valid, keep0, *, num_segments: int,
              dedup: bool, interpret: Optional[bool]):
    """Shared ascending-segment fold: pad the lanes to whole tiles, run
    the scan kernel, scatter each segment's last-lane total to its id
    (ids >= num_segments — the callers' padding — fall away)."""
    e = elabel.shape[0]
    need_rows = max(-(-e // _LANES), 8)
    rows = min(-(-need_rows // 8) * 8, _MAX_ROWS)
    tile_lanes = rows * _LANES
    total = -(-e // tile_lanes) * tile_lanes
    seg = _as_i32(seg)

    def lanes(x, fill):
        x = jnp.pad(_as_i32(x), (0, total - e), constant_values=fill)
        return x.reshape(total // _LANES, _LANES)

    spec = pl.BlockSpec((rows, _LANES), lambda i: (i, 0))
    hi, lo = pl.pallas_call(
        functools.partial(_seg_scan_kernel, dedup=dedup),
        grid=(total // tile_lanes,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 4,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((total // _LANES, _LANES),
                                        jnp.int32)] * 2,
        scratch_shapes=[pltpu.SMEM((8,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),   # the carry is serial
        interpret=interpret_mode(interpret),
    )(_as_i32(keep0).reshape(1), lanes(elabel, 0), lanes(pid_tgt, 0),
      lanes(seg, _SEG_PAD), lanes(valid, 0))
    hi = hi.reshape(-1)[:e]
    lo = lo.reshape(-1)[:e]
    is_last = jnp.concatenate([seg[1:] != seg[:-1], jnp.ones((1,), bool)])
    idx = jnp.where(is_last, seg, num_segments)

    def totals(x):
        return _as_u32(jnp.zeros(num_segments, jnp.int32).at[idx].set(
            x, mode="drop"))

    return totals(hi), totals(lo)


@functools.partial(jax.jit, static_argnames=("num_sigs", "interpret",
                                             "dedup"))
def frontier_sig_fold(elabel, pid_tgt, seg, valid, *, num_sigs: int,
                      dedup: bool = False,
                      interpret: Optional[bool] = None):
    """Maintenance frontier fold over a gathered batch.

    `seg` is the ascending frontier position of each edge (padded entries
    carry seg >= num_sigs and valid False); with ``dedup=True`` the lanes
    arrive (seg, eLabel, pId)-sorted — the device lexsort upstream — and
    one survivor per triple is kept in-kernel.  Used by
    `core.signatures.frontier_signature_hashes` when kernels are
    requested.

    elabel/pid_tgt/seg: int-typed [E]; valid bool [E].
    Returns (seg_hi, seg_lo) u32 [num_sigs].
    """
    return _seg_fold(elabel, pid_tgt, seg, valid, jnp.ones((1,), bool),
                     num_segments=num_sigs, dedup=dedup,
                     interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "dedup", "interpret"))
def chunk_sig_fold(elabel, pid_tgt, seg, valid, keep0, *,
                   num_segments: int, dedup: bool = True,
                   interpret: Optional[bool] = None):
    """Oocore per-chunk fold: in-kernel dedup + hash + segment combine.

    One sorted-run chunk per call: `seg` holds dense ascending local
    source ids (the cumsum of new-source flags the streamer computes to
    extract `src_unique` anyway), `valid` masks the tail padding, and
    `keep0` (bool [1]) is the host's cross-chunk boundary decision —
    False when the chunk's first triple equals the previous chunk's
    last.  Bit-identical to the host keep-mask + `_fold_chunk`
    composition in `repro.exmem.build` (asserted by tests).

    elabel/pid_tgt/seg: int32 [E]; valid bool [E]; keep0 bool [1].
    Returns (seg_hi, seg_lo) u32 [num_segments].
    """
    return _seg_fold(elabel, pid_tgt, seg, valid, keep0,
                     num_segments=num_segments, dedup=dedup,
                     interpret=interpret)
