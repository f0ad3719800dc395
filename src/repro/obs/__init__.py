"""repro.obs — zero-dependency tracing + metrics for the whole engine.

Observability
-------------
Every layer of the out-of-core engine is instrumented with spans named
``layer.phase`` (see the taxonomy below).  Tracing is **off by
default**: with no tracer installed, `span()` / `event()` are one global
read + one branch, instrumented code never mutates any counter, and all
outputs (partitions, pid histories, IOStats dicts) are bit-identical to
an uninstrumented run — with tracing on *or* off.

Span taxonomy (``layer.phase``):

* ``launch.*``   — one umbrella span per launcher subcommand
  (``launch.build``, ``launch.update``, ``launch.recover``,
  ``launch.snapshot``).
* ``build.*``    — `build_bisim_oocore` per-level phases, each carrying
  ``level=j``: ``build.level`` (whole level, with IOStats deltas),
  ``build.join``, ``build.fold``, ``build.rank``, ``build.pid_write``.
  The in-memory `build_bisim`: ``build.upload`` (the graph's four
  columns to the device, ``nodes=``, ``edges=``), ``build.sync`` (each
  device->host transfer, ``path=`` and ``what=``; the fused build has
  exactly one) and the ``build.dispatch`` instant event per program
  launch.
* ``sort.*``     — `exmem.runs` external sort: ``sort.run_formation``
  (one span per formed run), ``sort.merge_pass`` / ``sort.merge_chunk``
  (k-way fan-in), ``sort.merge_to_file``.
* ``store.*``    — `SpillableSigStore` / `DeviceSigStore`:
  ``store.probe``, ``store.resolve`` (probe+mint, ``minted=`` attr),
  ``store.spill``, ``store.merge``, ``store.probe_device``,
  ``store.resolve_device``, ``store.merge_device`` (the dispatch of the
  device store's merge, which runs on after the span ends: ``minted=``,
  ``size=`` and ``capacity=`` after the merge, the novel ``bucket=``,
  and ``level=`` where the caller knows it).
* ``table.*``    — on-disk table scans/rewrites: ``table.scan`` (per
  chunk, on the prefetch reader lane), ``table.rewrite``.
* ``aio.*``      — async pipeline threads: ``aio.read_chunk`` (reader
  lane), ``aio.write_chunk`` (writer lane), ``aio.readahead`` /
  ``aio.save`` (pool lanes), and consumer-side ``aio.wait_read`` /
  ``aio.wait_write`` wait attribution.
* ``maint.*``    — `BisimMaintainer` updates: ``maint.apply_edges``
  (the backend's edge-table rewrite, ``op=``, ``edges=``), then
  ``maint.propagate`` per update, ``maint.level`` per level
  (``level=``, ``frontier=``, ``device=`` attrs), ``maint.rebuild``;
  on the device path ``maint.prepare`` (host dedup and padding of a
  frontier batch, ``edges=``, ``dedup=``), ``maint.sync`` (each
  device->host transfer, ``what=``) and the ``maint.dispatch`` instant
  event per program launch.
* ``quotient.*`` — `repro.quotient`: ``quotient.materialize`` and
  ``quotient.level`` (index build), ``quotient.patch`` (a maintenance
  batch absorbed; the ``quotient.epoch`` instant event marks the swap),
  ``quotient.query_wave`` per wave of path queries, inside it
  ``quotient.sync`` (the wave's mask fetch, ``bytes=``) and
  ``quotient.expand`` (mask to node ids, ``queries=``, ``nodes=``).
* ``wal.*``      — durability: ``wal.append``, ``wal.commit`` (fsync
  round), ``wal.replay``, ``wal.snapshot``, ``wal.restore``.
* ``fault.*``    — instant *events*, not spans: ``fault.point`` (each
  fired injection point), ``fault.transient`` / ``fault.crash`` /
  ``fault.torn`` (what the plan injected), ``fault.retry`` (each
  `with_retries` backoff).

A ``<layer>.sync`` span wraps one blocking device->host transfer
(``jax.device_get``, ``np.asarray`` or ``int`` of a device array): its
duration is the host time spent waiting on the device, which includes
whatever dispatched work the transfer waited for.  No span adds a sync
of its own.

Usage::

    from repro import obs
    with obs.tracing() as tracer:
        build_bisim_oocore(g, k, ...)
    obs.write_chrome_trace(tracer, "trace.json")   # load in Perfetto
    print(obs.MetricsReport.from_tracer(tracer).format())

The Chrome-trace export gives one labeled lane per aio worker thread,
so prefetch/write overlap is visible against the main thread's
fold/rank spans.  `MetricsReport` aggregates per-phase totals, a
per-level table, and p50/p99 per-span latencies, and owns the
launcher's stable ``io:`` / ``overlap:`` line formats.
"""
from .tracer import (NOOP_SPAN, Span, Tracer, current_tracer, event,
                     install_tracer, span, tracing)
from .export import (MetricsReport, chrome_trace, validate_chrome_trace,
                     write_chrome_trace)

__all__ = [
    "NOOP_SPAN", "Span", "Tracer", "current_tracer", "event",
    "install_tracer", "span", "tracing",
    "MetricsReport", "chrome_trace", "validate_chrome_trace",
    "write_chrome_trace",
]
