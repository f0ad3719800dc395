"""A closed loop of update batches through `BisimMaintainer` on the
in-memory backend with device propagation.

Traffic parameters: `k`, `batch_ops` (edge operations per window
batch), `cycle` (the kinds of one window step's batches, `add` or
`delete`), `warmup` (the set-up's batches, each a kind and its edge
operations), `limits`.  New edges are
drawn by the graph's own edge law and redrawn until new; deletes are
drawn uniformly from the present edges."""
from __future__ import annotations

import time

import numpy as np

from bench import common, graphs, reference


class EdgeBook:
    """The benchmark's own record of the graph under updates: the base
    edges (alive or deleted) and the new edges present, as int64 keys.
    It draws each batch of the traffic and applies it to itself; the
    maintenance driver hands the same batch to the program."""

    def __init__(self, g: graphs.EdgeSet, law: graphs.EdgeLaw):
        self.g, self.law = g, law
        self.base = self.keys(g.src, g.elabel, g.dst)
        self.alive = np.ones(self.base.size, bool)
        self.added: set = set()

    def keys(self, src, elabel, dst) -> np.ndarray:
        return graphs.edge_keys(self.g.num_nodes,
                                self.law.num_edge_labels, src, elabel, dst)

    def split(self, keys: np.ndarray):
        return graphs.split_keys(self.g.num_nodes,
                                 self.law.num_edge_labels, keys)

    def _in_base(self, keys: np.ndarray):
        """(index into the base keys, whether the key is a base edge):
        an edge is a base edge, alive or deleted, or in `added`."""
        i = np.minimum(np.searchsorted(self.base, keys), self.base.size - 1)
        return i, self.base[i] == keys

    def _exists(self, keys: np.ndarray) -> np.ndarray:
        i, hit = self._in_base(keys)
        in_added = np.fromiter((int(x) in self.added for x in keys), bool,
                               count=keys.size)
        return (hit & self.alive[i]) | in_added

    def new_edges(self, rng, count: int) -> np.ndarray:
        """`count` distinct edges not in the graph, redrawn until so."""
        keys = np.empty(0, np.int64)
        while keys.size < count:
            cand = self.keys(*self.law.edges(rng, count - keys.size))
            keys = np.concatenate([keys, cand[~self._exists(cand)]])
            _, first = np.unique(keys, return_index=True)
            keys = keys[np.sort(first)]
        return keys[:count]

    def old_edges(self, rng, count: int) -> np.ndarray:
        """`count` distinct present edges, drawn uniformly."""
        added = np.array(sorted(self.added), np.int64)
        n_base = int(self.alive.sum())
        picked: set = set()
        out = []
        while len(out) < count:
            r = int(rng.integers(n_base + added.size))
            if r < n_base:
                i = int(rng.integers(self.base.size))
                while not self.alive[i]:
                    i = int(rng.integers(self.base.size))
                key = int(self.base[i])
            else:
                key = int(added[r - n_base])
            if key not in picked:
                picked.add(key)
                out.append(key)
        return np.array(out, np.int64)

    def draw(self, kind: str, rng, count: int) -> np.ndarray:
        if kind == "add":
            return self.new_edges(rng, count)
        if kind == "delete":
            return self.old_edges(rng, count)
        raise ValueError(f"unknown batch kind: {kind!r}")

    def apply(self, kind: str, keys: np.ndarray) -> None:
        i, hit = self._in_base(keys)
        if kind == "add":
            self.alive[i[hit]] = True          # a deleted base edge is back
            self.added.update(int(x) for x in keys[~hit])
        else:
            self.alive[i[hit]] = False
            self.added.difference_update(int(x) for x in keys[~hit])

    def graph(self) -> graphs.EdgeSet:
        keys = np.concatenate([self.base[self.alive],
                               np.array(sorted(self.added), np.int64)])
        s, l, d = self.split(keys)
        return graphs.canonical(self.g.node_labels, s, l, d)


def replay(book: EdgeBook, traffic: dict, seed: int, batches: int):
    """Yield (kind, keys) of the warm-up batches and then the first
    `batches` window batches of a run with this seed, each applied to
    `book` as it is drawn."""
    warm = common.rng_for(seed, common.WARMUP)
    for kind, ops in traffic["warmup"]:
        keys = book.draw(kind, warm, int(ops))
        book.apply(kind, keys)
        yield kind, keys
    rng = common.rng_for(seed, common.TRAFFIC)
    cycle = traffic["cycle"]
    for b in range(batches):
        kind = cycle[b % len(cycle)]
        keys = book.draw(kind, rng, int(traffic["batch_ops"]))
        book.apply(kind, keys)
        yield kind, keys


class Driver(common.Driver):
    """A closed loop of update batches through `BisimMaintainer` on the
    in-memory backend with device propagation.  Batch i inserts new
    edges drawn by the graph's own edge law, or deletes present edges
    drawn uniformly, as the traffic's `cycle` says; a window step is one
    whole cycle."""

    def setup(self) -> None:
        from repro.core import BisimMaintainer
        self.g = self.make_graph()
        self.book = EdgeBook(self.g, self.law)
        self.m = BisimMaintainer(common.to_program_graph(self.g), self.k,
                                 mode=self.mode, device=True)
        if not self.m.device:
            raise RuntimeError("the maintainer did not take the device path")
        self.batches, self.ops, self.rebuilds = 0, 0, 0
        self.batch_s = []
        self.on_device = True
        self.stream = replay(self.book, self.traffic, self.seed, 1 << 62)
        self.warmup_s = []
        for _ in self.traffic["warmup"]:
            t0 = time.perf_counter()
            self._apply(*next(self.stream))
            self.warmup_s.append(time.perf_counter() - t0)
        self.rebuilds = 0

    def _apply(self, kind: str, keys: np.ndarray) -> None:
        s, l, d = self.book.split(keys)
        if kind == "add":
            rep = self.m.add_edges(s, l, d)
        else:
            rep = self.m.delete_edges(s, l, d)
        self.rebuilds += bool(rep.rebuilt)
        self.on_device &= bool(rep.device)

    def step(self) -> None:
        """One whole cycle of batches (three inserts and a delete): every
        step then does the same work, so where the window happens to end
        does not change the mix of batch kinds it measured."""
        for _ in self.traffic["cycle"]:
            kind, keys = next(self.stream)
            t0 = time.perf_counter()
            self._apply(kind, keys)
            self.batch_s.append(time.perf_counter() - t0)
            self.batches += 1
            self.ops += keys.size

    def end_to_end(self, seconds: float) -> dict:
        return {"updates_per_s": self.ops / seconds}

    def work(self) -> dict:
        return {"batches": self.batches, "ops": self.ops,
                "rebuilds": self.rebuilds, "batch_s": self.batch_s,
                "warmup_s": self.warmup_s}

    def attempted(self) -> int:
        return self.ops

    def release(self) -> None:
        self.got = [np.array(p, np.int64) for p in self.m.pids]
        self.device_stayed = bool(self.m.device) and self.on_device
        del self.m

    def check(self):
        want = reference.bisim_levels(self.book.graph(), self.k,
                                      early_stop=False)
        values = {"mismatched_blocks":
                  reference.history_mismatch(self.got, want),
                  "left_device": int(not self.device_stayed)}
        bad = any(values.values())
        return self.checks(values), (self.ops if bad else 0)


def control(d: Driver, steps: int, **_options) -> None:
    """Counting bisimulation (`multiset=True`) in place of the set
    semantics that the configuration states, as the maintained history
    after the warm-up and `steps` window cycles of this seed's batches."""
    d.g = d.make_graph()
    d.book = EdgeBook(d.g, d.law)
    warm = len(d.traffic["warmup"])
    batches = steps * len(d.traffic["cycle"])
    d.ops = sum(keys.size for i, (_, keys) in enumerate(
        replay(d.book, d.traffic, d.seed, batches)) if i >= warm)
    d.got = reference.bisim_levels(d.book.graph(), d.k, early_stop=False,
                                   multiset=True)
    d.device_stayed = True
