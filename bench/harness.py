"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell needs is found by name: the cell in `BENCHMARK.json`,
its configuration in `configs/<config>.json` (which names its graph
family in `generators/<generator>.py`), its traffic in
`traffic/<traffic>.json` (which names its driver in
`drivers/<driver>.py`), and each per-layer metric's reader in
`metrics/<metric>.py`.  Adding a cell adds entries and files; it edits
none.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """XLA programs compiled or loaded from the persistent cache, and the
    seconds spent tracing, lowering and compiling, summed from jax's
    own monitoring events (from any thread), with the seconds of each
    function and the persistent cache's hits and misses."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.compiled: list = []            # function of each compile
        self.by_function: dict = {}
        self.cache = {"hits": 0, "misses": 0}
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, fun_name: str = "?",
                 **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration
                self.by_function[fun_name] = \
                    self.by_function.get(fun_name, 0.0) + duration
                if event == _BACKEND_COMPILE:
                    self.compiles += 1
                    self.compiled.append(fun_name)

    def count(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            kind = event.rsplit("_", 1)[-1]
            if kind in self.cache:
                with self._lock:
                    self.cache[kind] += 1

    def slowest(self, n: int = 5) -> list:
        return sorted(self.by_function.items(), key=lambda kv: -kv[1])[:n]


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def load_cell(spec: dict, name: str):
    """(cell, configuration, traffic) of the workload `name`."""
    cell = find_cell(spec, name)
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, conf["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def scaled(config: dict, traffic: dict, overrides: Optional[dict]):
    """The configuration and traffic with a test's `overrides`: its
    `params` replace the configuration's, its `traffic` keys the
    traffic's."""
    overrides = overrides or {}
    config = dict(config, params=dict(config["params"],
                                      **overrides.get("params", {})))
    return config, dict(traffic, **overrides.get("traffic", {}))


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries that this cell reports."""
    out = []
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end":
            out.append(m)
        else:
            moved = e2e[m["moves"]]
            if "workloads" not in moved or cell in moved["workloads"]:
                out.append(m)
    return out


def load_reader(metric: str):
    """The `read(run)` function of `metrics/<metric>.py`."""
    from bench.common import load_module
    return load_module("metrics", metric).read


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader reads."""
    window_compiles: int
    work: dict
    peaks: dict
    trace: object = None            # trace.Reduced, traced runs only
    spans: list = dataclasses.field(default_factory=list)  # repro.obs


def device_info(chips: int) -> dict:
    """The platform must be a TPU with at least `chips` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: jax reports {devs[0].platform!r} devices, "
                         "not a TPU; the benchmark runs on the chip only")
    if len(devs) < chips:
        raise SystemExit(f"bench: {len(devs)} TPU chips, {chips} needed")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["kinds"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def written_bytes() -> Optional[int]:
    """Bytes this process has caused to be written to storage."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def _memory_peak(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_window(driver, seconds: float, clock: CompileClock, *,
               annotate: bool = False):
    """Steps until the first completion after `seconds`; returns
    (elapsed seconds, the functions compiled inside the window)."""
    import jax
    c0 = len(clock.compiled)
    t0 = time.perf_counter()
    while True:
        if annotate:
            with jax.profiler.TraceAnnotation("bench.step"):
                driver.step()
        else:
            driver.step()
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return elapsed, clock.compiled[c0:]


def traced_window(driver, seconds: float, clock: CompileClock):
    """The window under the profiler and a `repro.obs` tracer; returns
    (elapsed, functions compiled, reduced trace, the tracer's spans on
    the trace's clock)."""
    import jax
    from repro.obs import tracer as obs
    from . import trace as trace_mod
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        tracer = obs.Tracer()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with obs.tracing(tracer), \
                    jax.profiler.TraceAnnotation(trace_mod.WINDOW), \
                    tracer.span(trace_mod.WINDOW):
                elapsed, compiles = run_window(driver, seconds, clock,
                                               annotate=True)
        finally:
            jax.profiler.stop_trace()
        # the obs tracer's clock, moved onto the trace's by the window
        # span that both recorded
        ours = [s for s in tracer.spans if s["name"] == trace_mod.WINDOW][0]
        spans = [s for s in tracer.spans if s["name"] != trace_mod.WINDOW]

        def host_spans(w0):
            shift = w0 - ours["ts"]
            return [(s["name"], s["ts"] + shift, s["ts"] + shift + s["dur"])
                    for s in spans]

        red = trace_mod.reduce_trace(log_dir, extra_spans=host_spans)
        return elapsed, compiles, red, spans
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, spec: Optional[dict] = None, device=None,
        overrides: Optional[dict] = None, compile_cache: bool = True,
        out=sys.stdout) -> dict:
    """One run; prints the earlier lines and the checks (stderr) and
    returns the result object.  `device` None means: look for the chip
    (the command line); tests hand in a stand-in, toy `overrides` (see
    `scaled`) and leave the compile cache alone."""
    import jax
    from repro.compat import use_compile_cache
    spec = spec or load_spec()
    cell, config, traffic = load_cell(spec, workload)
    config, traffic = scaled(config, traffic, overrides)
    if device is None:
        device = device_info(int(cell["chips"]))
    peaks = peaks_for(device["kind"])
    cache = None
    if compile_cache:
        # every program of the cell, however quick to compile, is kept
        # so that only the first run in a checkout compiles
        cache = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    jax.monitoring.register_event_listener(clock.count)
    try:
        return _run(workload, seed, seconds, trace, t_start, spec, cell,
                    config, traffic, device, peaks, cache, clock, out)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
        jax.monitoring.unregister_event_listener(clock.count)


def _run(workload, seed, seconds, trace, t_start, spec, cell, config,
         traffic, device, peaks, cache, clock, out) -> dict:
    from bench.common import make_driver
    driver = make_driver(config, traffic, seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    setup_compiles = clock.compiles
    print(json.dumps({"phase": "setup", "setup_s": setup_s,
                      "compiles": setup_compiles,
                      "compile_s": clock.seconds, "cache": cache,
                      "cache_hits": clock.cache["hits"],
                      "cache_misses": clock.cache["misses"],
                      "slowest_compiles": clock.slowest(),
                      "written_bytes": written_bytes()}),
          file=out, flush=True)

    red, spans = None, []
    if trace:
        elapsed, compiles, red, spans = traced_window(driver, seconds, clock)
    else:
        elapsed, compiles = run_window(driver, seconds, clock)
    print(json.dumps({"phase": "window", "seconds": elapsed,
                      "window_compiles": len(compiles),
                      "window_compiled": compiles, **driver.work()}),
          file=out, flush=True)
    memory_peak = _memory_peak(int(cell["chips"]))
    dev = dict(device, memory_peak_bytes=memory_peak)

    if trace:
        record = RunRecord(len(compiles), driver.work(), peaks, red, spans)
        metrics = {}
        for m in metrics_for(spec, workload, "per_layer"):
            value = load_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
    else:
        values = dict(driver.end_to_end(elapsed), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_for(spec, workload, "end_to_end")}
    attempted = driver.attempted()

    driver.release()
    t_check = time.perf_counter()
    checks, failed = driver.check()
    check_s = time.perf_counter() - t_check
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(json.dumps({"phase": "check", "seconds": check_s,
                      "written_bytes": written_bytes()}), file=out,
          flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = red.breakdown()
    result["checks"] = checks
    return result
