"""Plain reference for the checks that decide `correct`.

Nothing here imports the program.  It computes the k-bisimulation
partition of an `EdgeSet` level by level in numpy (Definition 1 of the
paper, set semantics: a node's level-j signature is its node label and
the *set* of (edge label, level-(j-1) block of the target) pairs of its
out-edges) and answers path queries directly on the graph.

Signature sets are keyed by two independent 64-bit hashes (a splitmix64
finaliser of each element, summed per node), so two distinct sets share
a key with probability about n^2 / 2^129: no collision in any run.
The controls that `control.py` reads use the same code with one
guarantee broken: `key_bits=32` keeps only 32 bits of the key (the
precision below the program's 64-bit signature keys), and
`multiset=True` counts repeated pairs (counting bisimulation, not the
set semantics the configurations state).
"""
from __future__ import annotations

import numpy as np

from .graphs import EdgeSet

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)
_SALT_A = np.uint64(0x243F6A8885A308D3)
_SALT_B = np.uint64(0x13198A2E03707344)


def _mix(x: np.ndarray, salt: np.uint64) -> np.ndarray:
    """splitmix64's finaliser of x + salt."""
    with np.errstate(over="ignore"):
        z = x.astype(np.uint64) + salt * _GOLD
        z = (z ^ (z >> np.uint64(30))) * _M1
        z = (z ^ (z >> np.uint64(27))) * _M2
        return z ^ (z >> np.uint64(31))


def dense_rank(*cols: np.ndarray) -> np.ndarray:
    """Rank rows by the given key columns (first column most significant):
    equal rows get equal ranks 0..n_distinct-1."""
    n = cols[0].shape[0]
    if n == 0:
        return np.zeros(0, np.int64)
    order = np.lexsort(cols[::-1])
    new = np.zeros(n, bool)
    new[0] = True
    for c in cols:
        s = c[order]
        new[1:] |= s[1:] != s[:-1]
    rank = np.empty(n, np.int64)
    rank[order] = np.cumsum(new) - 1
    return rank


def bisim_levels(g: EdgeSet, k: int, *, early_stop: bool,
                 key_bits: int = 128, multiset: bool = False) -> list:
    """Block ids of every node at levels 0..k (int64 arrays); with
    `early_stop` the list ends at the first level whose block count
    equals its predecessor's, as the paper's build does."""
    n = g.num_nodes
    src = g.src.astype(np.int64)
    lab = g.elabel.astype(np.int64)
    pid0 = dense_rank(g.node_labels.astype(np.int64))
    levels = [pid0]
    for _ in range(k):
        prev = levels[-1]
        elem = lab * np.int64(n) + prev[g.dst]
        if not multiset:
            # the set of (label, block) pairs: drop repeats per source
            key = np.unique(src * np.int64(n * (int(lab.max(initial=0)) + 1))
                            + elem)
            width = np.int64(n * (int(lab.max(initial=0)) + 1))
            s, e = key // width, key % width
        else:
            s, e = src, elem
        h1 = np.zeros(n, np.uint64)
        h2 = np.zeros(n, np.uint64)
        if s.size:
            starts = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
            owners = s[starts]
            with np.errstate(over="ignore"):
                h1[owners] = np.add.reduceat(_mix(e, _SALT_A), starts)
                h2[owners] = np.add.reduceat(_mix(e, _SALT_B), starts)
        has = np.zeros(n, np.uint64)
        if s.size:
            has[np.unique(s)] = 1
        if key_bits < 64:
            mask = np.uint64((1 << key_bits) - 1)
            cols = (pid0, (h1 ^ has) & mask)
        else:
            cols = (pid0, h1, h2, has)
        pid = dense_rank(*cols)
        levels.append(pid)
        if early_stop and _count(pid) == _count(prev):
            break
    return levels


def _count(pid: np.ndarray) -> int:
    return int(pid.max()) + 1 if pid.size else 0


def mismatched_blocks(got, want) -> int:
    """0 iff the two labelings induce the same partition; otherwise how
    far apart they are: (#distinct pairs - #blocks of want) + (#distinct
    pairs - #blocks of got), so a merge or a split of blocks counts."""
    got = np.asarray(got).astype(np.int64).ravel()
    want = np.asarray(want).astype(np.int64).ravel()
    if got.shape != want.shape:
        return max(got.size, want.size)
    if got.size == 0:
        return 0
    rg = dense_rank(got)
    rw = dense_rank(want)
    pairs = _count(dense_rank(rg * np.int64(_count(rw)) + rw))
    return (pairs - _count(rw)) + (pairs - _count(rg))


def history_mismatch(got_levels, want_levels) -> int:
    """`mismatched_blocks` summed over the levels of two partition
    histories; a level that only one side has counts all of its blocks."""
    both = min(len(got_levels), len(want_levels))
    extra = [lv for side in (got_levels, want_levels) for lv in side[both:]]
    return int(sum(mismatched_blocks(got_levels[j], want_levels[j])
                   for j in range(both))
               + sum(_count(np.asarray(lv)) for lv in extra))


# ------------------------------------------------------------- queries
def path_answer(g: EdgeSet, labels, src_label=None, tgt_label=None):
    """Ascending ids of the nodes with an outgoing path spelling
    `labels`, whose first node has `src_label` and last `tgt_label`
    (each optional): backward chaining over the edge list."""
    n = g.num_nodes
    mask = (np.ones(n, bool) if tgt_label is None
            else g.node_labels == tgt_label)
    for lab in reversed(tuple(labels)):
        sel = (g.elabel == lab) & mask[g.dst]
        mask = np.zeros(n, bool)
        mask[g.src[sel]] = True
    if src_label is not None:
        mask &= g.node_labels == src_label
    return np.flatnonzero(mask).astype(np.int64)


def quotient_path_answer(g: EdgeSet, levels, level: int, labels,
                         src_label=None, tgt_label=None):
    """The same query answered over the quotient of the given partition
    history (block masks chained down the levels, then expanded to
    nodes): equal to `path_answer` when the history is the exact
    k-bisimulation and len(labels) <= level."""
    m = len(labels)
    nb = [_count(p) for p in levels]
    base = levels[level - m]
    bmask = np.ones(nb[level - m], bool)
    if tgt_label is not None:
        bmask = np.zeros(nb[level - m], bool)
        bmask[base[g.node_labels == tgt_label]] = True
    for t in range(m - 1, -1, -1):
        lev = level - t
        hit = (g.elabel == labels[t]) & bmask[levels[lev - 1][g.dst]]
        bmask = np.zeros(nb[lev], bool)
        bmask[levels[lev][g.src[hit]]] = True
    mask = bmask[levels[level]]
    if src_label is not None:
        mask &= g.node_labels == src_label
    return np.flatnonzero(mask).astype(np.int64)
