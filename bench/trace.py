"""Reduce a `jax.profiler` trace of a benchmark window to its numbers.

`reduce_trace` reads the `.xplane.pb` file with `ProfileData.from_file`
and returns a `Reduced`:

* busy time: the union of the intervals in which an XLA operation ran
  on a chip (the "XLA Ops" line of each `/device:TPU:<n>` plane),
  clipped to the window and averaged over the chips;
* device time per program: the "XLA Modules" events summed by program
  name (the trailing "(<id>)" dropped), clipped to the window;
* device time per operation ("<program>/<op>"), for the breakdown;
* the longest idle gaps between busy intervals, each attributed to
  the innermost host span open at its midpoint.

The window is the host annotation named `window_name` (the harness
wraps its measured window in `bench.window`).  Host spans are that
plane's other annotations plus any spans handed in on the same clock
(the harness converts the program's `repro.obs` spans).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM_ID = re.compile(r"\(\d+\)$")

WINDOW = "bench.window"


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]          # ns, on the trace's clock
    chips: int
    busy_s: float                        # averaged over chips
    program_s: Dict[str, float]          # summed over chips
    op_s: Dict[str, float]               # summed over chips
    gaps: List[Tuple[str, float]]        # (host span, seconds), longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def idle_share(self) -> Optional[float]:
        """Percent of the window in which no operation ran on a chip."""
        if self.chips == 0 or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def program_seconds(self, pattern: str) -> Optional[float]:
        """Device seconds of the programs whose name matches `pattern`
        (a regular expression searched in the name); None if none ran."""
        rx = re.compile(pattern)
        hits = [s for name, s in self.program_s.items() if rx.search(name)]
        return sum(hits) if hits else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s: float, e: float, w0: float, w1: float):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _op_name(text: str, modules, starts, t: float) -> str:
    """"<program>/<op>" for an "XLA Ops" event whose name is the op's
    HLO text ("%fusion.1 = pred[...] fusion(...)")."""
    op = text.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return f"{modules[i][2]}/{op}"
    return op


def _innermost(spans, t: float) -> str:
    """Name of the span covering t that started last (the innermost of
    properly nested spans), or "none"."""
    best, best_start = "none", None
    for name, s, e in spans:
        if s <= t < e and (best_start is None or s >= best_start):
            best, best_start = name, s
    return best


def reduce_trace(path: str, *, window_name: str = WINDOW,
                 extra_spans=(), top_gaps: int = 10) -> Reduced:
    """Reduce the trace at `path` (an `.xplane.pb` file or a profiler
    log directory).  `extra_spans` are (name, start_ns, end_ns) host
    spans on the trace's clock, or a function of the window's start that
    returns them."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    host_spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.name, ev.start_ns, ev.end_ns))
    wins = [(s, e) for n, s, e in host_spans if n == window_name]
    if not wins:
        raise ValueError(f"no {window_name!r} annotation in {path}")
    w0, w1 = max(wins, key=lambda w: w[1] - w[0])
    spans = [sp for sp in host_spans if sp[0] != window_name]
    if callable(extra_spans):
        extra_spans = extra_spans(w0)
    spans += list(extra_spans)

    busy_total = 0.0
    program_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    raw_gaps: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops_line = lines.get("XLA Ops")
        mod_line = lines.get("XLA Modules")
        intervals = []
        modules = []                 # (start, end, program name)
        if mod_line is not None:
            for ev in mod_line.events:
                name = _PROGRAM_ID.sub("", ev.name)
                modules.append((ev.start_ns, ev.end_ns, name))
                c = _clip(ev.start_ns, ev.end_ns, w0, w1)
                if c:
                    program_s[name] = program_s.get(name, 0.0) + \
                        (c[1] - c[0]) / 1e9
                    if ops_line is None:
                        intervals.append(c)
        modules.sort()
        starts = [m[0] for m in modules]
        if ops_line is not None:
            for ev in ops_line.events:
                c = _clip(ev.start_ns, ev.end_ns, w0, w1)
                if c:
                    intervals.append(c)
                    name = _op_name(ev.name, modules, starts, ev.start_ns)
                    op_s[name] = op_s.get(name, 0.0) + (c[1] - c[0]) / 1e9
        busy = _union(intervals)
        busy_total += sum(e - s for s, e in busy) / 1e9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        raw_gaps += [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                     if e > s]
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_innermost(spans, (s + e) / 2), (e - s) / 1e9)
            for s, e in raw_gaps[:top_gaps]]
    n = len(devices)
    return Reduced(window=(w0, w1), chips=n,
                   busy_s=busy_total / n if n else 0.0,
                   program_s=program_s, op_s=op_s, gaps=gaps)
