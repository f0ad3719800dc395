"""Device-resident maintenance propagation (paper §4 on the accelerator).

`BisimMaintainer._propagate` recomputes frontier signatures and resolves
them against the per-level store S.  The host path does both in
vectorized numpy (`hashes_np` + `SigStore`); this module is the device
path the maintainer switches to with ``device=True``:

  * `frontier_fold` — pads a gathered frontier batch to power-of-two
    buckets and folds it into sig hash pairs with the same mix-hash
    lanes as construction (one jitted program per (edge-bucket,
    node-bucket) shape).  Stage placement is adaptive and per-call
    overridable: the set-semantics dedup sort (``device_sort``) and the
    segment wrap-sum (``device_segsum``) run in-program on accelerators
    but through numpy on CPU backends, where XLA's comparator sort and
    sequential prefix sum measurably lose while its fused elementwise
    hash measurably wins.  A per-frontier cache keeps the fold's device
    constants (labels, boundaries, pId_0) resident across levels.  In
    multiset mode with ``use_kernel=True`` the fold routes through the
    Pallas `kernels.sig_fold.frontier_sig_fold` (tiled segmented scan).

  * `DeviceSigStore` — a device mirror of the array-backed `SigStore`:
    the sorted (hi, lo) u32 key lanes and the int32 pid column live as
    device arrays padded to a power-of-two capacity with all-ones
    sentinels.  `probe_mint_insert` resolves a batch: a sort-free
    binary-search probe on device, first-occurrence minting of the
    misses on host (`sig_store.mint_novel`, the host store's own rule,
    over the few missing keys), and a sort-free merge by rank of the
    novel keys on device, dispatched only when something is novel.  The
    old columns are donated back to XLA on accelerators.  Results are
    bit-identical to `SigStore.get_or_assign` (same probe keys -> same
    pids, same next_pid), so device and host propagation agree
    bit-for-bit.  The host `SigStore` is re-materialized lazily
    (`to_host`) only when the store is extracted — between updates the
    columns never leave the device.

  * `resident_level_resolve` — the cross-level maintenance residency
    program: fold + probe + changed count for one propagation level
    fused into a single dispatch, returning one scalar to the host in
    the steady state; the probe lanes cross back only for levels where
    something actually changed, where the misses are minted and merged.
    `resident_levels_resolve` runs every level in one dispatch while
    nothing changes.  No device program of maintenance holds a sort, so
    each new frontier bucket compiles in seconds on the TPU, where a
    sort of 2^16+ elements takes tens of seconds to compile.

Keys are kept as two u32 lanes (not fused u64) because JAX runs without
x64 and TPU vector units are 32-bit; lexicographic (hi, lo) order equals
the host store's sorted u64 order, so `split_key`/`fuse_key` round-trip
the columns exactly.

Shape discipline: probe batches and store capacities are bucketed to
powers of two, so the number of distinct XLA programs is O(log^2 N) over
a session, not O(updates).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import hashes_np
from . import signatures as sig
from .sig_store import SigStore, fuse_key, mint_novel, split_key
from ..obs import tracer as obs

_I32_MAX = np.iinfo(np.int32).max
_SENT = jnp.uint32(0xFFFFFFFF)


# Default bucket floor: shapes below this collapse into one bucket, which
# bounds the number of compiled programs for tiny batches.  Callers that
# care about padding waste on small batches can pass a smaller floor.
BUCKET_FLOOR = 8
# Floor of the novel-key batch a store merge takes.
_NOVEL_FLOOR = 1 << 12


def bucket(n: int, floor: "int | None" = None) -> int:
    """Smallest power of two >= max(n, floor) (jit shape bucketing).

    ``floor`` (default `BUCKET_FLOOR`) must be a power of two.  For
    n >= floor the padding waste is strictly under 2x (the next power of
    two above n is < 2n), and the number of distinct buckets — hence
    compiled XLA programs — is O(log(max_n)) per call site; below the
    floor everything shares one bucket, trading at most floor/n padding
    on tiny batches for a single compiled program.
    """
    if floor is None:
        floor = BUCKET_FLOOR
    if floor < 1 or (floor & (floor - 1)):
        raise ValueError(f"bucket floor must be a power of two, got {floor}")
    b = floor
    while b < n:
        b <<= 1
    return b


def _prepare_batch(pid0_vals, seg, elabel, pid_tgt, num_sigs: int, *,
                   dedup: bool, bounds, device_sort):
    """Host-side prep for `frontier_fold`: dtype narrowing, optional
    host-placed dedup, bucket padding.

    Returns (p0, lab_p, tgt_p, bounds_p, seg_or_None, e, dedup_on_device)
    — seg is materialized (padded) only when the device program still
    needs it (device-placed dedup sort or the Pallas kernel route).
    """
    e = int(np.asarray(elabel).shape[0])
    with obs.span("maint.prepare", edges=e, dedup=dedup):
        # 4-byte columns up front: the hash lanes wrap to u32 anyway
        # (bit-compatible for these non-negative inputs), and both
        # numpy's lexsort and the transfer move half the bytes
        seg = np.asarray(seg).astype(np.int32, copy=False)
        lab = np.asarray(elabel).astype(np.uint32, copy=False)
        tgt = np.asarray(pid_tgt).astype(np.uint32, copy=False)
        if bounds is None and e and (np.diff(seg) < 0).any():
            # the gathers emit edges in (sorted) frontier order; the
            # device segment combine (segment_wrapsum) relies on it.  A
            # caller passing `bounds` asserts the grouping itself.
            raise ValueError("frontier_fold requires ascending seg ids")
        nb = bucket(num_sigs)
        if device_sort is None:
            # XLA CPU's comparator sort is several times slower than
            # numpy's lexsort; on accelerators the sort belongs in the
            # program
            device_sort = jax.default_backend() != "cpu"
        if dedup and not device_sort:
            # host dedup: the numpy path's exact lexsort + boundary mask,
            # compressing the batch before it ever crosses to the device
            order = np.lexsort((tgt, lab, seg))
            sseg, slab, stgt = seg[order], lab[order], tgt[order]
            keep = np.ones(e, dtype=bool)
            keep[1:] = ((sseg[1:] != sseg[:-1]) | (slab[1:] != slab[:-1])
                        | (stgt[1:] != stgt[:-1]))
            seg, lab, tgt = sseg[keep], slab[keep], stgt[keep]
            e = int(seg.shape[0])
            bounds = None  # boundaries moved; recompute below
            dedup = False
        if bounds is None:
            bounds = np.searchsorted(seg, np.arange(num_sigs + 1))
        eb = bucket(e)
        lab_p = np.empty(eb, np.uint32)
        lab_p[:e] = lab
        lab_p[e:] = 0
        tgt_p = np.empty(eb, np.uint32)
        tgt_p[:e] = tgt
        tgt_p[e:] = 0
        p0 = np.zeros(nb, np.uint32)
        p0[:num_sigs] = np.asarray(pid0_vals).astype(np.uint32)
        bounds_p = np.full(nb + 1, e, np.int32)  # empty padding segments
        bounds_p[: num_sigs + 1] = bounds
        seg_p = None
        if dedup:
            # >= num_sigs: sorts last, and falls out of the segment sum
            seg_p = np.full(eb, nb, np.int32)
            seg_p[:e] = seg
        return p0, lab_p, tgt_p, bounds_p, seg_p, e, dedup


@jax.jit
def _edge_hash_pairs(elabel, pid_tgt):
    """Per-edge signature hash lanes, fused on device — the one fold
    stage that is faster under XLA on every backend (one pass, no numpy
    temporaries)."""
    return sig.hash_pair(elabel, pid_tgt)


def _host_segsum_fold(lab_dev, tgt_p, seg, p0_vals, e: int, num_sigs: int):
    """CPU arrangement of the fold: per-edge hash on device, wrap-add
    combine + final mix on host (`np.add.at` beats XLA CPU's sequential
    prefix sum).  Returns host (hi, lo) padded to ``bucket(num_sigs)``
    so downstream probe shapes match the all-device arrangement."""
    e_hi, e_lo = _edge_hash_pairs(lab_dev, jnp.asarray(tgt_p))
    with obs.span("maint.sync", what="edge_hash", edges=e):
        e_hi = np.asarray(e_hi)[:e]
        e_lo = np.asarray(e_lo)[:e]
    seg_hi = np.zeros(num_sigs, np.uint32)
    seg_lo = np.zeros(num_sigs, np.uint32)
    if e:
        with np.errstate(over="ignore"):
            np.add.at(seg_hi, seg[:e], e_hi)
            np.add.at(seg_lo, seg[:e], e_lo)
    hi, lo = hashes_np.hash_triple(seg_hi, seg_lo, np.asarray(p0_vals))
    nb = bucket(num_sigs)
    hi_p = np.zeros(nb, np.uint32)
    hi_p[:num_sigs] = hi
    lo_p = np.zeros(nb, np.uint32)
    lo_p[:num_sigs] = lo
    return hi_p, lo_p


def frontier_fold(pid0_vals, seg, elabel, pid_tgt, num_sigs: int, *,
                  dedup: bool = True, use_kernel: bool = False,
                  bounds=None, device_sort: "bool | None" = None,
                  device_segsum: "bool | None" = None,
                  cache: "dict | None" = None, cache_key=None):
    """Fold a gathered frontier batch into sig hash pairs on device.

    Same contract as `hashes_np.signatures_from_edges` (and bit-identical
    to it; `seg` must be ascending, as the gathers produce), but returns
    *device* u32 arrays of length ``bucket(num_sigs)`` — entries past
    ``num_sigs`` are padding garbage.  The caller can feed them straight
    into `DeviceSigStore.get_or_assign_pairs` with ``count=num_sigs``
    without a host round-trip.

    ``bounds`` optionally passes the [num_sigs+1] segment boundaries when
    the gather already knows them (CSR offsets); otherwise one host
    searchsorted recovers them.  ``device_sort`` places the set-semantics
    dedup sort: on accelerators it runs inside the jitted program; on CPU
    backends (the default decision when None) it runs through numpy's
    lexsort first and the deduplicated batch takes the segless device
    fold, which also shrinks the transfer.  Either placement keeps
    bit-parity: the dedup survivors are identical.

    ``device_segsum`` places the segment wrap-sum: in-program via
    `segment_wrapsum` on accelerators, on the host (``np.add.at`` over
    the device-hashed lanes) on CPU backends, where XLA's sequential
    prefix sum loses to numpy's fused scatter-add — measured, like the
    sort placement; the per-edge hash stays on device either way.

    ``cache`` (with ``cache_key``, an array identifying the frontier)
    keeps the sort-free route's per-batch device constants — padded
    labels, boundaries, pId_0 — resident between calls: propagation hits
    every level with the same frontier while only pId_{j-1} changes, so
    a hit transfers one column instead of four.  The dedup routes
    reorder per level and bypass the cache.  The caller owns
    invalidation on graph/pId_0 mutation.
    """
    if device_segsum is None:
        device_segsum = jax.default_backend() != "cpu"
    use_cache = (cache is not None and cache_key is not None
                 and not dedup and not use_kernel)
    if use_cache and cache.get("key") is not None \
            and cache["e"] == int(np.asarray(pid_tgt).shape[0]) \
            and cache.get("segsum") == device_segsum \
            and np.array_equal(cache["key"], cache_key):
        e = cache["e"]
        eb = cache["lab_dev"].shape[0]
        tgt_p = np.empty(eb, np.uint32)
        tgt_p[:e] = np.asarray(pid_tgt).astype(np.uint32, copy=False)
        tgt_p[e:] = 0
        if not device_segsum:
            return _host_segsum_fold(
                cache["lab_dev"], tgt_p, np.asarray(seg), cache["p0"], e,
                num_sigs)
        return sig.frontier_signature_hashes_presorted(
            cache["p0_dev"], cache["lab_dev"], jnp.asarray(tgt_p),
            cache["bounds_dev"], jnp.int32(e),
            num_sigs=cache["p0_dev"].shape[0])
    p0, lab_p, tgt_p, bounds_p, seg_p, e, dedup_dev = _prepare_batch(
        pid0_vals, seg, elabel, pid_tgt, num_sigs, dedup=dedup,
        bounds=bounds, device_sort=device_sort)
    nb = p0.shape[0]
    if not dedup_dev and not use_kernel:
        lab_dev = jnp.asarray(lab_p)
        if not device_segsum:
            # CPU: the dedup (if any) already ran on host above; hash on
            # device, combine on host
            if use_cache:
                cache.update(key=np.asarray(cache_key).copy(), e=e,
                             segsum=False, lab_dev=lab_dev,
                             p0=np.asarray(pid0_vals))
            if dedup:  # host-deduplicated batch: seg was compressed too
                seg = None  # recovered from bounds below
            return _host_segsum_fold(
                lab_dev, tgt_p,
                np.asarray(seg) if seg is not None else
                np.repeat(np.arange(num_sigs),
                          np.diff(bounds_p[: num_sigs + 1])),
                np.asarray(pid0_vals), e, num_sigs)
        p0_dev = jnp.asarray(p0)
        bounds_dev = jnp.asarray(bounds_p)
        if use_cache:
            # the padded device columns are frontier constants
            cache.update(key=np.asarray(cache_key).copy(), e=e,
                         segsum=True, p0_dev=p0_dev, lab_dev=lab_dev,
                         bounds_dev=bounds_dev)
        return sig.frontier_signature_hashes_presorted(
            p0_dev, lab_dev, jnp.asarray(tgt_p), bounds_dev,
            jnp.int32(e), num_sigs=nb)
    if seg_p is None:  # kernel route without dedup: seg not padded yet
        eb = lab_p.shape[0]
        seg_p = np.full(eb, nb, np.int32)
        seg_p[:e] = np.asarray(seg).astype(np.int32, copy=False)
    return sig.frontier_signature_hashes(
        jnp.asarray(p0), jnp.asarray(seg_p), jnp.asarray(lab_p),
        jnp.asarray(tgt_p), jnp.asarray(bounds_p), jnp.int32(e),
        num_sigs=nb, dedup=dedup, use_kernel=use_kernel)


def _searchsorted_pairs(khi, klo, qhi, qlo):
    """'left' insertion positions of (qhi, qlo) into the sorted pair
    columns (khi, klo): a vectorized branchless binary search (the
    capacity is static, so the step count unrolls to log2(cap)+1)."""
    cap = khi.shape[0]
    lo = jnp.zeros(qhi.shape, jnp.int32)
    hi = jnp.full(qhi.shape, cap, jnp.int32)

    def body(_, bounds):
        lo, hi = bounds
        cont = lo < hi  # converged lanes must stay put (fixed step count)
        mid = (lo + hi) >> 1
        vh = khi[mid]
        vl = klo[mid]
        less = (vh < qhi) | ((vh == qhi) & (vl < qlo))  # store key < probe
        return (jnp.where(cont & less, mid + 1, lo),
                jnp.where(cont & ~less, mid, hi))

    lo, hi = jax.lax.fori_loop(0, int(cap).bit_length(), body, (lo, hi))
    return lo


def _probe_core(khi, klo, kpid, qhi, qlo, count, size):
    """Shared probe: binary search + gather.  Returns (valid, found, out)
    with out = stored pid where found, -1 elsewhere."""
    cap = khi.shape[0]
    p = qhi.shape[0]
    valid = jnp.arange(p, dtype=jnp.int32) < count
    idx = _searchsorted_pairs(khi, klo, qhi, qlo)
    idxc = jnp.minimum(idx, cap - 1)
    found = (khi[idxc] == qhi) & (klo[idxc] == qlo) & (idx < size) & valid
    out = jnp.where(found, kpid[idxc], jnp.int32(-1))
    return valid, found, out


@jax.jit
def _probe_step(khi, klo, kpid, qhi, qlo, count, size):
    """The store probe: binary search + gather, no sort.  Returns the
    stored pid where found, -1 elsewhere."""
    return _probe_core(khi, klo, kpid, qhi, qlo, count, size)[2]


def _merge_step_impl(khi, klo, kpid, nhi, nlo, npid, n_novel, size, *,
                     new_cap: int):
    """Merge `n_novel` sorted novel keys (the leading lanes of nhi/nlo/
    npid; all-ones sentinels past them) into the sorted store columns;
    re-bucket to `new_cap`.

    Both inputs are ordered, so the merge is by rank, with no sort: a
    store key lands at its index plus the novel keys below it, a novel
    key at its own index plus the store keys below it (the two sets are
    disjoint: a novel key is by definition missing from S).  Binary
    searches and one scatter per column; the padding sentinels never
    count as "below" a real key, so a genuine all-ones key keeps its
    pid.  Sort-free also means cheap to compile for every probe bucket —
    a TPU sort of a multi-million-entry store compiles for minutes."""
    cap = khi.shape[0]
    p = nhi.shape[0]
    novel_pos = jnp.arange(p, dtype=jnp.int32)
    dest_new = jnp.where(
        novel_pos < n_novel,
        novel_pos + jnp.minimum(_searchsorted_pairs(khi, klo, nhi, nlo),
                                size),
        new_cap)
    store_pos = jnp.arange(cap, dtype=jnp.int32)
    dest_old = jnp.where(
        store_pos < size,
        store_pos + jnp.minimum(_searchsorted_pairs(nhi, nlo, khi, klo),
                                n_novel),
        new_cap)

    def merged(fill, old, new):
        out = jnp.full((new_cap,), fill, old.dtype)
        out = out.at[dest_old].set(old, mode="drop")
        return out.at[dest_new].set(new, mode="drop")

    return (merged(_SENT, khi, nhi), merged(_SENT, klo, nlo),
            merged(0, kpid, npid))


_merge_step_jits: dict = {}


def _merge_step(khi, klo, kpid, *rest, new_cap: int):
    """Jit `_merge_step_impl` lazily, donating the old columns where XLA
    can reuse them in place: on accelerators (CPU ignores donation and
    warns), and only at an unchanged capacity (a regrown store cannot
    alias its old buffers).  Deciding at the first call keeps the backend
    query out of import time, as `partition._bisim_step` does."""
    donate = jax.default_backend() != "cpu" and new_cap == khi.shape[0]
    fn = _merge_step_jits.get(donate)
    if fn is None:
        fn = _merge_step_jits[donate] = jax.jit(
            _merge_step_impl, static_argnames=("new_cap",),
            donate_argnums=(0, 1, 2) if donate else ())
    return fn(khi, klo, kpid, *rest, new_cap=new_cap)


def _mint_misses(dstore, out: np.ndarray, qhi: np.ndarray, qlo: np.ndarray,
                 next_pid: int, level: "int | None" = None) -> int:
    """The host half of a resolve: the probe lanes the device store
    missed (``out < 0``) get pids minted exactly as
    `SigStore.get_or_assign` mints them (`mint_novel`: one per distinct
    key, in order of first occurrence), written into `out` in place; the
    novel keys are merged into the device columns.  Returns next_pid'.

    Minting is a sort of the missing keys, which stays on the host: the
    device programs of maintenance hold no sort at all, so each new
    frontier bucket compiles in seconds.  `level`, where the caller
    knows it, labels the merge's span."""
    miss = out < 0
    if not miss.any():
        return next_pid
    ukeys, new_pids, inv = mint_novel(fuse_key(qhi[miss], qlo[miss]),
                                      next_pid)
    if next_pid + ukeys.shape[0] > _I32_MAX:
        raise OverflowError(
            "device store pid space exceeded int32; rebuild to "
            "re-densify pids")
    out[miss] = new_pids[inv]
    dstore.insert_sorted(ukeys, new_pids, level=level)
    return next_pid + int(ukeys.shape[0])


def _settle_level(dstore, qhi, qlo, out, old_pid, num_sigs: int,
                  next_pid: int, level: "int | None" = None):
    """A level whose pids changed: pull its probe lanes, mint the misses
    on host, merge them into the store.  Returns ((pids int64, changed
    bool, n_changed), next_pid')."""
    # whole bucket-padded lanes, trimmed on host: slicing on device
    # would compile one more program per frontier length
    with obs.span("maint.sync", what="level_deltas", keys=num_sigs):
        out_h, qh, ql = (np.asarray(x)[:num_sigs]
                         for x in jax.device_get((out, qhi, qlo)))
    pj = out_h.astype(np.int64)
    next_pid = _mint_misses(dstore, pj, qh, ql, next_pid, level)
    changed = pj != np.asarray(old_pid)
    return (pj, changed, int(changed.sum())), next_pid


@jax.jit
def _level_resident_step(p0, lab, tgt, bounds, e_count, khi, klo, kpid,
                         size, old_pid, count):
    """One maintenance level as ONE program: presorted/deduplicated fold
    (hash lanes + segment wrap-sum + final mix), store probe and the
    changed count — so the steady state of propagation transfers one
    scalar per level.  A missing key counts as changed: the pid it will
    be minted is at least next_pid, above every pid in use."""
    nb = p0.shape[0]
    qhi, qlo = sig.frontier_signature_hashes_presorted(
        p0, lab, tgt, bounds, e_count, num_sigs=nb)
    valid, _found, out = _probe_core(khi, klo, kpid, qhi, qlo, count, size)
    n_changed = jnp.sum(valid & (out != old_pid)).astype(jnp.int32)
    return qhi, qlo, out, n_changed


@jax.jit
def _levels_resident_step(p0, count, labs, tgts, boundss, es, olds,
                          stores, sizes):
    """ALL maintenance levels as ONE program (one dispatch per k-loop).
    Levels unroll at trace time — a `lax.scan` cannot carry the
    per-level store columns, whose capacities differ — but the compiled
    artifact is still a single XLA dispatch whose steady-state sync is
    the stacked changed counts.

    Level j's fold consumes pId_{j-1} of the frontier targets *as
    uploaded before the dispatch*, which is only valid while earlier
    levels changed nothing: the host trusts the results up to and
    including the FIRST level with a nonzero count and re-runs the rest
    through the per-level ladder.

    `labs`/`boundss`/`es` are either shared across levels (1-D / scalar:
    the multiset route, where the fold constants are frontier-only) or
    stacked per level (the set-semantics routes, where the host dedup
    reorders each level differently); the discrimination is static.
    """
    k = tgts.shape[0]
    n_changeds, per_level = [], []
    for j in range(k):
        lab = labs if labs.ndim == 1 else labs[j]
        bounds = boundss if boundss.ndim == 1 else boundss[j]
        e = es if es.ndim == 0 else es[j]
        khi, klo, kpid = stores[j]
        nb = p0.shape[0]
        qhi, qlo = sig.frontier_signature_hashes_presorted(
            p0, lab, tgts[j], bounds, e, num_sigs=nb)
        valid, _found, out = _probe_core(khi, klo, kpid, qhi, qlo, count,
                                         sizes[j])
        n_changeds.append(jnp.sum(valid & (out != olds[j]))
                          .astype(jnp.int32))
        per_level.append((qhi, qlo, out))
    return jnp.stack(n_changeds), tuple(per_level)


def resident_levels_resolve(dstores, pid0_vals, seg, elabel, tgts,
                            num_sigs: int, olds, next_pids, *,
                            dedup: bool = True, bounds=None,
                            cache: "dict | None" = None, cache_key=None):
    """Resolve ALL propagation levels in one dispatch (the fused k-loop).

    ``dstores``/``tgts``/``olds``/``next_pids`` are per-level (level j =
    index j-1): `tgts[j]` is pId_j(tgt) of the frontier's out-edge
    targets, `olds[j]` the frontier's current pId_{j+1} column.  The
    shared fold constants (pId_0, labels, boundaries) upload once — and
    on the multiset route stay device-resident across *calls* through
    the same ``cache`` the per-level `resident_level_resolve` uses.

    Returns ``(nclean, dirty, next_pid_d)``:

      * nclean  — number of leading levels confirmed unchanged (their
        pids, stores and next_pid are untouched by construction);
      * dirty   — None when every level is clean, else the per-level
        resident-result triple ``(pj int64, changed bool, n_changed)``
        for level ``nclean + 1``, whose inputs were still valid; its
        novel keys are already minted and merged into its store;
      * next_pid_d — the (possibly advanced) next_pid of that dirty
        level, or None when dirty is None.

    Levels past the first dirty one must be recomputed by the caller
    (their uploaded target pids were stale the moment something
    changed).  A no-change propagation costs exactly ONE dispatch and
    ONE k-vector scalar sync for the whole k-loop.
    """
    k = len(tgts)
    e = int(np.asarray(elabel).shape[0])
    nb = bucket(num_sigs)
    use_cache = cache is not None and cache_key is not None and not dedup
    if not dedup:
        if use_cache and cache.get("key") is not None \
                and cache["e"] == e \
                and np.array_equal(cache["key"], cache_key):
            p0_dev = cache["p0_dev"]
            lab_dev = cache["lab_dev"]
            bounds_dev = cache["bounds_dev"]
            eb = lab_dev.shape[0]
        else:
            p0, lab_p, _tgt_p, bounds_p, _seg_p, e, _dd = _prepare_batch(
                pid0_vals, seg, elabel, tgts[0], num_sigs, dedup=False,
                bounds=bounds, device_sort=False)
            eb = lab_p.shape[0]
            p0_dev = jnp.asarray(p0)
            lab_dev = jnp.asarray(lab_p)
            bounds_dev = jnp.asarray(bounds_p)
            if use_cache:
                cache.update(key=np.asarray(cache_key).copy(), e=e,
                             p0_dev=p0_dev, lab_dev=lab_dev,
                             bounds_dev=bounds_dev)
        tgt_stack = np.zeros((k, eb), np.uint32)
        for j in range(k):
            tgt_stack[j, :e] = np.asarray(tgts[j]).astype(np.uint32,
                                                          copy=False)
        labs, boundss, es = lab_dev, bounds_dev, np.int32(e)
    else:
        # set semantics: the exact host lexsort dedup, per level (the
        # survivors depend on the level's target pids)
        cols = [_prepare_batch(pid0_vals, seg, elabel, tgts[j], num_sigs,
                               dedup=True, bounds=bounds,
                               device_sort=False)
                for j in range(k)]
        eb = max(c[1].shape[0] for c in cols)
        labs_h = np.zeros((k, eb), np.uint32)
        tgt_stack = np.zeros((k, eb), np.uint32)
        boundss_h = np.zeros((k, nb + 1), np.int32)
        es_h = np.zeros(k, np.int32)
        for j, (p0, lab_p, tgt_p, bounds_p, _sp, e_j, _dd) in \
                enumerate(cols):
            labs_h[j, : lab_p.shape[0]] = lab_p
            tgt_stack[j, : tgt_p.shape[0]] = tgt_p
            boundss_h[j] = bounds_p
            es_h[j] = e_j
        p0_dev = jnp.asarray(cols[0][0])
        labs, boundss, es = labs_h, boundss_h, es_h
    old_stack = np.zeros((k, nb), np.int32)
    for j in range(k):
        old_stack[j, :num_sigs] = np.asarray(olds[j]).astype(np.int32,
                                                             copy=False)
    obs.event("maint.dispatch", what="levels_resident", keys=num_sigs,
              levels=k)
    nchs_d, per_level = _levels_resident_step(
        p0_dev, np.int32(num_sigs), labs, tgt_stack, boundss, es,
        old_stack, tuple((d.khi, d.klo, d.kpid) for d in dstores),
        np.asarray([d.size for d in dstores], np.int32))
    # THE steady-state sync: one k-vector of scalars for the whole loop
    with obs.span("maint.sync", what="levels_scalars", keys=num_sigs,
                  levels=k):
        nchs = np.asarray(jax.device_get(nchs_d))
    dirty_lvls = np.flatnonzero(nchs > 0)
    if dirty_lvls.size == 0:
        return k, None, None
    d = int(dirty_lvls[0])
    qhi, qlo, out = per_level[d]
    dirty, next_pid_d = _settle_level(dstores[d], qhi, qlo, out, olds[d],
                                      num_sigs, int(next_pids[d]),
                                      level=d + 1)
    return d, dirty, next_pid_d


def resident_level_resolve(dstore, pid0_vals, seg, elabel, pid_tgt,
                           num_sigs: int, old_pid, next_pid: int, *,
                           dedup: bool = True, bounds=None,
                           cache: "dict | None" = None, cache_key=None,
                           level: "int | None" = None):
    """Fold + probe + changed count for one propagation level in one
    dispatch (the per-level residency path).

    Bit-identical to `frontier_fold` + `SigStore.get_or_assign` + the
    host ``old != new`` comparison: the set-semantics dedup runs on host
    exactly as the host path's lexsort would, every device op is the
    same integer arithmetic, and misses are minted by the same
    `mint_novel`.  Returns

        (pids int64 [num_sigs] | None, changed bool [num_sigs] | None,
         n_changed, next_pid')

    where the arrays are None iff n_changed == 0 — the per-level pid
    deltas only cross back to host for levels that actually changed.
    ``cache``/``cache_key`` keep the multiset route's per-frontier device
    constants (pId_0, labels, boundaries) resident across levels, like
    `frontier_fold`'s cache (dedup modes reorder per level and bypass
    it).  ``level`` labels the store merge's span.
    """
    use_cache = cache is not None and cache_key is not None and not dedup
    if use_cache and cache.get("key") is not None \
            and cache["e"] == int(np.asarray(pid_tgt).shape[0]) \
            and np.array_equal(cache["key"], cache_key):
        # hit: the fold constants (pId_0, labels, boundaries) are already
        # device-resident for this frontier; only the tgt column moves
        e = cache["e"]
        p0_dev = cache["p0_dev"]
        lab_dev = cache["lab_dev"]
        bounds_dev = cache["bounds_dev"]
        eb = lab_dev.shape[0]
        nb = p0_dev.shape[0]
        tgt_p = np.empty(eb, np.uint32)
        tgt_p[:e] = np.asarray(pid_tgt).astype(np.uint32, copy=False)
        tgt_p[e:] = 0
    else:
        p0, lab_p, tgt_p, bounds_p, _seg_p, e, _dd = _prepare_batch(
            pid0_vals, seg, elabel, pid_tgt, num_sigs, dedup=dedup,
            bounds=bounds, device_sort=False)
        nb = p0.shape[0]
        p0_dev = jnp.asarray(p0)
        lab_dev = jnp.asarray(lab_p)
        bounds_dev = jnp.asarray(bounds_p)
        if use_cache:
            cache.update(key=np.asarray(cache_key).copy(), e=e,
                         p0_dev=p0_dev, lab_dev=lab_dev,
                         bounds_dev=bounds_dev)
    old_p = np.zeros(nb, np.int32)
    old_p[:num_sigs] = np.asarray(old_pid).astype(np.int32, copy=False)
    obs.event("maint.dispatch", what="level_resident", keys=num_sigs)
    qhi, qlo, out, n_changed_d = _level_resident_step(
        p0_dev, lab_dev, jnp.asarray(tgt_p), bounds_dev, jnp.int32(e),
        dstore.khi, dstore.klo, dstore.kpid, jnp.int32(dstore.size),
        jnp.asarray(old_p), jnp.int32(num_sigs))
    # THE steady-state sync: one scalar per level
    with obs.span("maint.sync", what="level_scalar", keys=num_sigs):
        n_changed = int(n_changed_d)
    if n_changed == 0:
        return None, None, 0, next_pid
    (pj, changed, n_changed), next_pid = _settle_level(
        dstore, qhi, qlo, out, old_pid, num_sigs, next_pid, level)
    return pj, changed, n_changed, next_pid


class DeviceSigStore:
    """Device mirror of one level's `SigStore` (sorted key/pid columns as
    device arrays; probe + merge-insert run on device).

    The mirror is authoritative once created: every resolve goes through
    it, and the host `SigStore` is re-materialized lazily by `to_host()`
    (cached until the next insert dirties it) — the paper's S leaves the
    device only on store extraction.
    """

    __slots__ = ("khi", "klo", "kpid", "size", "_host")

    def __init__(self, host: SigStore):
        keys = np.asarray(host.keys)
        pids = np.asarray(host.pids)
        if pids.size and int(pids.max()) > _I32_MAX:
            raise OverflowError(
                "device store mirrors pids as int32; rebuild to re-densify")
        self.size = int(keys.shape[0])
        cap = bucket(self.size)
        hi, lo = split_key(keys)
        khi = np.full(cap, 0xFFFFFFFF, np.uint32)
        klo = np.full(cap, 0xFFFFFFFF, np.uint32)
        kpid = np.zeros(cap, np.int32)
        khi[:self.size] = hi
        klo[:self.size] = lo
        kpid[:self.size] = pids.astype(np.int32)
        self.khi = jnp.asarray(khi)
        self.klo = jnp.asarray(klo)
        self.kpid = jnp.asarray(kpid)
        self._host = host

    def __len__(self) -> int:
        return self.size

    # ------------------------------------------------------------- resolve
    def probe_mint_insert(self, qhi, qlo, count: int,
                          next_pid: int) -> tuple[np.ndarray, int]:
        """Resolve probe keys: device probe, host minting of the misses,
        device merge-insert of the novel keys (only when something is
        novel) — one probe dispatch and one sync per call.

        `qhi`/`qlo` may be device arrays straight out of `frontier_fold`
        (no host round-trip before the probe) or bucket-padded numpy
        arrays; only the first `count` entries are real probes.  Returns
        (pids int64 [count], next_pid') — bit-identical to
        `SigStore.get_or_assign` on the fused keys (asserted by
        tests/test_fused_build.py).
        """
        if next_pid + count > _I32_MAX:
            raise OverflowError(
                "device store pid space exceeded int32; rebuild to "
                "re-densify pids")
        qhi = jnp.asarray(qhi)
        qlo = jnp.asarray(qlo)
        with obs.span("store.resolve_device", keys=count) as sp:
            obs.event("maint.dispatch", what="probe", keys=count)
            out = _probe_step(
                self.khi, self.klo, self.kpid, qhi, qlo, jnp.int32(count),
                jnp.int32(self.size))
            with obs.span("maint.sync", what="probe", keys=count):
                out_h, qh, ql = (np.asarray(x)[:count]
                                 for x in jax.device_get((out, qhi, qlo)))
            out_h = out_h.astype(np.int64)
            nxt = _mint_misses(self, out_h, qh, ql, next_pid)
            sp.set(minted=nxt - next_pid)
        return out_h, nxt

    def insert_sorted(self, ukeys: np.ndarray, pids: np.ndarray, *,
                      level: "int | None" = None) -> None:
        """Merge sorted, distinct keys that are not in the store (with
        their pids) into the device columns: one sort-free merge
        dispatch.  The capacity only grows, in power-of-two buckets; the
        novel batch is bucketed from `_NOVEL_FLOOR` up, so the store's
        merge compiles a handful of shapes per session (its cost is the
        store's length, not the batch's).  The `store.merge_device` span
        times the dispatch only (the merge runs on after it returns) and
        records the capacity the merge walks; `level` labels it."""
        n = int(ukeys.shape[0])
        p = bucket(n, _NOVEL_FLOOR)
        hi, lo = split_key(ukeys)
        nhi = np.full(p, 0xFFFFFFFF, np.uint32)
        nlo = np.full(p, 0xFFFFFFFF, np.uint32)
        npid = np.zeros(p, np.int32)
        nhi[:n] = hi
        nlo[:n] = lo
        npid[:n] = pids
        new_size = self.size + n
        cap = self.khi.shape[0]
        new_cap = cap if new_size <= cap else bucket(new_size)
        with obs.span("store.merge_device", minted=n, size=new_size,
                      capacity=new_cap, bucket=p) as sp:
            if level is not None:
                sp.set(level=level)
            self.khi, self.klo, self.kpid = _merge_step(
                self.khi, self.klo, self.kpid, nhi, nlo, npid, np.int32(n),
                np.int32(self.size), new_cap=new_cap)
        self.size = new_size
        self._host = None  # mirrored back lazily on extraction

    def get_or_assign_pairs(self, qhi, qlo, count: int,
                            next_pid: int) -> tuple[np.ndarray, int]:
        """Bulk get-or-assign over bucket-padded (hi, lo) probe lanes —
        the fused `probe_mint_insert` under its historical name."""
        return self.probe_mint_insert(qhi, qlo, count, next_pid)

    def get_or_assign_keys(self, keys, next_pid: int) -> tuple[np.ndarray,
                                                               int]:
        """Host-key entry point (fused u64 keys, e.g. level-0 label keys):
        split, bucket-pad, resolve on device."""
        keys = np.asarray(keys, dtype=np.uint64)
        count = int(keys.shape[0])
        p = bucket(count)
        hi, lo = split_key(keys)
        qhi = np.zeros(p, np.uint32)
        qlo = np.zeros(p, np.uint32)
        qhi[:count] = hi
        qlo[:count] = lo
        return self.get_or_assign_pairs(qhi, qlo, count, next_pid)

    # ------------------------------------------------------------ mirroring
    def to_host(self) -> SigStore:
        """Materialize the mirrored store on host (sorted u64 keys + int64
        pids — the exact `SigStore` the host path would hold)."""
        if self._host is None:
            kh, kl, kp = jax.device_get((self.khi, self.klo, self.kpid))
            self._host = SigStore(
                fuse_key(kh[: self.size], kl[: self.size]),
                np.asarray(kp[: self.size], dtype=np.int64), presorted=True)
        return self._host
