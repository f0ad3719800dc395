"""Back-to-back in-memory builds of one graph (`build_bisim`, the fused
path); each call uploads the edges and fetches the history.

Traffic parameters: `k`, `sample_builds` (how many of the window's
builds are compared besides its first and last), `limits`."""
from __future__ import annotations

import numpy as np

from bench import bytecount, common, reference


class Driver(common.Driver):

    def setup(self) -> None:
        from repro.core import build_bisim
        self.g = self.make_graph()
        self.graph = common.to_program_graph(self.g)
        self._build = lambda: build_bisim(self.graph, self.k, mode=self.mode)
        self.first = self._build().pids          # warm: compiles or loads
        self._window()

    def _window(self) -> None:
        self.builds, self.iterations = 0, []
        self.kept = common.Reservoir(int(self.traffic["sample_builds"]),
                                     common.rng_for(self.seed, common.SAMPLE))
        self.last = None

    def step(self) -> None:
        res = self._build()
        self.builds += 1
        self.iterations.append(res.k_effective)
        self.kept.offer(res.pids)
        self.last = res.pids

    def end_to_end(self, seconds: float) -> dict:
        return {"build_edges_per_s":
                self.g.num_edges * self.builds / seconds}

    def work(self) -> dict:
        return {"builds": self.builds,
                "hbm_bytes": sum(bytecount.build_bytes(
                    self.g.num_nodes, self.g.num_edges, it)
                    for it in self.iterations)}

    def attempted(self) -> int:
        return self.builds

    def release(self) -> None:
        del self.graph, self._build

    def check(self):
        want = reference.bisim_levels(self.g, self.k, early_stop=True)
        got = [self.first] + list(self.kept.items)
        if self.last is not None:
            got.append(self.last)
        checked, worst, failed = [], 0, 0
        for hist in got:
            if any(np.array_equal(hist, c) for c in checked):
                continue            # identical to a history checked already
            checked.append(hist)
            m = reference.history_mismatch(list(hist), want)
            failed += m > 0
            worst = max(worst, m)
        return self.checks({"mismatched_blocks": worst}), failed


def control(d: Driver, steps: int, **_options) -> None:
    """Counting bisimulation (`multiset=True`) in place of the set
    semantics that the configuration states, as the history of the
    set-up build and of each of `steps` window builds."""
    d.g = d.make_graph()
    d._window()
    hist = reference.bisim_levels(d.g, d.k, early_stop=True, multiset=True)
    d.first = hist
    for _ in range(steps):
        d.builds += 1
        d.kept.offer(hist)
        d.last = hist
