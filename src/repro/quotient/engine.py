"""Fixed-slot batched quotient query evaluator — the `serve/engine.py`
wave idiom applied to structural queries.

Path queries are bucketed by (level, hop count): every query in a
bucket walks the same level ladder, so a wave of up to ``max_batch``
of them shares ONE jitted dispatch per hop (a [B, n_blocks] block mask
advanced over the level's device-resident edge columns) and ONE
device->host sync per wave (the final mask fetch).  Padding slots carry
the WANT_NONE sentinel label, which matches no block.  Point lookups
never touch the device: they are host `searchsorted` over the extent
runs.

A hop needs no scatter.  Each level's edges are in canonical (src,
elabel, dst) order, so the out-edges of source block P are the
contiguous run [off[P], off[P+1]) of the level's CSR offsets, computed
on the host at `refresh` (which refuses a level whose `src` is not
non-decreasing: the hop rests on it).  "Some out-edge of P is a hit" is
then a prefix sum of the [B, Eq] hit matrix differenced at the offsets.
The prefix sum runs in blocks of ``_SCAN_BLOCK`` edges, an int16 scan
inside each block plus an int32 scan of the block totals: XLA's
one-axis cumsum over millions of edges compiles for a TPU in anything
from one second to minutes, depending on the length.  The hop holds
the [B, Eq] bool hits and their [B, Eq] int16 in-block sums (plus the
compiler's relayouts): for a v5e the compiler reserves 1.3 GB at B = 16
and 6M quotient edges, 2.3 GB at the default ``max_batch`` of 64 — well
inside one chip's 16 GB.

The compiled-program cache is keyed by the level shapes, so a steady
artifact compiles O(k) hop programs once; a maintenance patch that
changes a level's edge or block count recompiles that level's hop only.

Engine answers are bit-identical to `queries.eval_ref`: both compute
the same boolean masks (an integer count of hits per block is exact,
and > 0 is the OR) and share `expand_blocks` for the mask -> node-id
step — asserted by the differential tests.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import tracer as obs

from .queries import (WANT_ALL, WANT_NONE, PointLookup, expand_blocks,
                      normalize_query, point_lookup)


@jax.jit
def _init_mask(labels: jnp.ndarray, want: jnp.ndarray) -> jnp.ndarray:
    """[B, n] endpoint mask: WANT_ALL slots match every block, real
    labels match their blocks, WANT_NONE (padding) matches none."""
    return (want[:, None] == WANT_ALL) | (labels[None, :] == want[:, None])


# edges per block of the hop's prefix sum; an in-block sum fits int16
_SCAN_BLOCK = 1024
# the label of the hop's padding edges: equal to no label a hop asks for
_NO_LABEL = np.iinfo(np.int32).min


@jax.jit
def _hop(mask_tgt: jnp.ndarray, elabel: jnp.ndarray, dst: jnp.ndarray,
         off: jnp.ndarray, want: jnp.ndarray) -> jnp.ndarray:
    """One backward hop for a whole wave: block P survives for slot b
    iff some edge (P, want[b], Q) has mask_tgt[b, Q].

    The level's edges are src-sorted and P's out-edges are the run
    [off[P], off[P+1]), so P's hit count is a difference of the prefix
    sum of the [B, Eq] hits, gathered once at the n_src + 1 offsets.
    One padding edge in front makes the sum at index i count the hits
    before edge i; the tail pads to whole blocks and is never read."""
    b, e = mask_tgt.shape[0], dst.shape[0]
    pad = (1, -(e + 1) % _SCAN_BLOCK)
    el = jnp.pad(elabel, pad, constant_values=_NO_LABEL)
    hit = mask_tgt[:, jnp.pad(dst, pad)] & (el[None, :] == want[:, None])
    inner = jnp.cumsum(hit.reshape(b, -1, _SCAN_BLOCK), axis=2,
                       dtype=jnp.int16)
    tot = inner[:, :, -1].astype(jnp.int32)
    base = jnp.cumsum(tot, axis=1) - tot
    at = inner.reshape(b, -1)[:, off] + base[:, off // _SCAN_BLOCK]
    return at[:, 1:] > at[:, :-1]


def _src_offsets(src: np.ndarray, n_src: int) -> np.ndarray:
    """int32 [n_src + 1] CSR offsets of a src-sorted edge column: the
    out-edges of block P are [off[P], off[P+1])."""
    if src.size and np.any(src[1:] < src[:-1]):
        raise ValueError("quotient level edges are not sorted by src")
    return np.searchsorted(src, np.arange(n_src + 1)).astype(np.int32)


class _EpochView:
    """One epoch's immutable serving state: the host columns the answer
    path reads (duck-typing the `QuotientIndex` attributes that
    `expand_blocks` / `point_lookup` touch) plus the device-array dicts.
    `QuotientEngine.refresh` builds a fresh view and publishes it with
    one reference assignment — a query that pinned the previous view
    keeps reading a complete, never-mutated epoch while a patch lands."""

    __slots__ = ("epoch", "k", "counts", "labels", "runs",
                 "dev_levels", "dev_labels")

    def __init__(self, epoch, k, counts, labels, runs,
                 dev_levels, dev_labels):
        self.epoch = int(epoch)
        self.k = int(k)
        self.counts = counts
        self.labels = labels
        self.runs = runs
        self.dev_levels = dev_levels
        self.dev_labels = dev_labels


class QuotientEngine:
    """Serves one `QuotientIndex` snapshot.  ``epoch`` names the
    snapshot every answer was computed against (the service bumps it
    atomically with the device-array swap).

    Admission is epoch-pinned: `query` captures the current `_EpochView`
    once and answers entirely from it, so queries admitted while a
    maintenance patch is being absorbed read the pre-patch epoch instead
    of stalling behind the patch — `refresh`/`rebind` are the only swap
    points, and the swap is a single atomic reference assignment."""

    def __init__(self, index, *, max_batch: int = 64):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.index = index
        self.max_batch = int(max_batch)
        self.epoch = int(index.epoch)
        self.stats = dict(waves=0, hops=0, queries=0, point_lookups=0)
        self._dev_levels: Dict[int, tuple] = {}
        self._dev_labels: Dict[int, jnp.ndarray] = {}
        self._view: _EpochView = None
        self.refresh()

    # ------------------------------------------------------------ snapshot
    def refresh(self, levels=None) -> None:
        """(Re-)upload level edge columns, their src offsets and block
        labels; with ``levels`` only those (a patch's touched set), else
        all.  Raises ValueError on a level not sorted by src.  The
        caller patches the host index first (copy-on-write: pinned
        arrays are never scribbled on); this swap is the one atomic
        point where new queries start seeing the new epoch."""
        idx = self.index
        dev_levels = dict(self._dev_levels)
        dev_labels = dict(self._dev_labels)
        lvls = range(1, idx.k + 1) if levels is None else sorted(levels)
        for j in lvls:
            L = idx.levels[j]
            dev_levels[j] = (jnp.asarray(L.elabel), jnp.asarray(L.dst),
                             jnp.asarray(_src_offsets(L.src, idx.counts[j])))
        labs = range(idx.k + 1) if levels is None else sorted(
            set(levels) | {j - 1 for j in levels})
        for j in labs:
            if 0 <= j <= idx.k:
                dev_labels[j] = jnp.asarray(idx.labels[j])
        self._dev_levels = dev_levels
        self._dev_labels = dev_labels
        # the atomic swap: a single reference assignment under the GIL
        self._view = _EpochView(
            int(idx.epoch), idx.k, tuple(int(c) for c in idx.counts),
            list(idx.labels), list(idx.runs), dev_levels, dev_labels)
        self.epoch = int(idx.epoch)

    def rebind(self, index) -> None:
        """Point the engine at a replacement index (rematerialization):
        drop every cached device array and re-upload from scratch."""
        self.index = index
        self._dev_levels = {}
        self._dev_labels = {}
        self.refresh()

    # -------------------------------------------------------------- serve
    def query(self, queries: List) -> List:
        """Evaluate a batch of queries; answers keep input order.  Path
        queries return ascending node-id arrays, `PointLookup` returns
        a `PointAnswer`.  The whole batch is answered against the epoch
        current at admission (pinned once, here)."""
        view = self._view
        answers: List = [None] * len(queries)
        buckets: Dict[tuple, list] = {}
        for i, q in enumerate(queries):
            if isinstance(q, PointLookup):
                answers[i] = point_lookup(view, q.node, q.level)
                self.stats["point_lookups"] += 1
                continue
            labels, src_l, tgt_l, level = normalize_query(q, view.k)
            buckets.setdefault((level, len(labels)), []).append(
                (i, labels, src_l, tgt_l))
        for (j, m), items in sorted(buckets.items()):
            for w0 in range(0, len(items), self.max_batch):
                self._run_wave(view, j, m, items[w0:w0 + self.max_batch],
                               answers)
        return answers

    def _run_wave(self, view: _EpochView, j: int, m: int, wave: list,
                  answers: list) -> None:
        B = self.max_batch
        with obs.span("quotient.query_wave", level=j, hops=m,
                      batch=len(wave), epoch=view.epoch):
            want = np.full(B, WANT_NONE, dtype=np.int32)
            for s, (_, _, _, tgt_l) in enumerate(wave):
                want[s] = WANT_ALL if tgt_l is None else tgt_l
            mask = _init_mask(view.dev_labels[j - m], jnp.asarray(want))
            for t in range(m - 1, -1, -1):
                lev = j - t
                el, dst, off = view.dev_levels[lev]
                assert off.shape[0] == view.counts[lev] + 1
                lab_t = np.full(B, WANT_NONE, dtype=np.int32)
                for s, (_, labels, _, _) in enumerate(wave):
                    lab_t[s] = labels[t]
                mask = _hop(mask, el, dst, off, jnp.asarray(lab_t))
                self.stats["hops"] += 1
            # the wave's one device->host sync
            with obs.span("quotient.sync", bytes=mask.nbytes):
                host = np.asarray(mask)
            self.stats["waves"] += 1
            with obs.span("quotient.expand", queries=len(wave)) as sp:
                nodes = 0
                for s, (i, _, src_l, _) in enumerate(wave):
                    answers[i] = expand_blocks(view, j, host[s], src_l)
                    nodes += answers[i].size
                    self.stats["queries"] += 1
                sp.set(nodes=nodes)
