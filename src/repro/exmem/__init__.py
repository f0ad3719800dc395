"""External-memory subsystem: graph size independent of RAM (paper §3-§4).

The source paper's contribution is an *I/O-efficient* k-bisimulation
algorithm whose construction cost is `O(k·sort(|E_t|) + k·scan(|N_t|) +
sort(|N_t|))` over disk-resident tables, with maintenance under updates in
`O(k·sort(|E_t|) + k·sort(|N_t|))`.  This package is the reproduction of
that regime; each module maps onto a paper construct:

  runs.py    §3.1's two I/O primitives. `external_sort` is `sort(X)`:
             run formation over memory-sized chunks plus a bounded-budget
             k-way merge of memory-mapped `.npy` runs (the emit-boundary
             merge loop itself is `repro.core.kway`, shared with the
             spillable store and the table updates); `IOStats` is the
             cost model (`sort_cost`/`scan_cost` record counters plus
             byte traffic); `rebuffer` keeps runs budget-sized.

  tables.py  §2 Tables 2-3. `OocGraph` holds N_t and E_t as chunked
             on-disk column tables in the two sort orders Algorithm 1
             consumes: E_tst by (sId, eLabel, tId) and E_tts by
             (tId, sId).  `Graph.to_ooc()` / `OocGraph.to_memory()`
             convert; `save`/`load` fix the directory format.  The
             tables are maintainable in place: `append_nodes`,
             `insert_edges` (kway merge), `delete_edges` and
             `compact_rows` (filtered scans).

  build.py   §3.2 Algorithm 1 as a streamed pipeline
             (`build_bisim_oocore`): sequential merge join of E_tts
             against the sorted pId_{j-1} file (lines 9-11), external
             re-sort of the joined records (line 12), per-chunk dedup +
             device fold via the jitted signature hash/segment-sum step
             (lines 13-15), and global ranking through a
             `SpillableSigStore` — `core.sig_store`'s §3.2 sorted
             signature file S with spill-to-disk runs (lines 16-18).
             ``keep_stores=True`` hands the per-level stores to the
             maintenance backend instead of deleting them.

  aio.py     the async I/O pipeline — the paper's "overlap I/O with
             computation" as a first-class subsystem.  Contracts:
             `PrefetchReader` wraps any chunk iterator with a bounded
             (``prefetch_depth``) one-chunk-ahead background thread and
             stays iterator-compatible (producer exceptions re-raise at
             the consumer; ``close()`` joins the thread, also on
             abandonment).  `StreamingWriter` double-buffers appends to a
             known-length ``.npy`` file and publishes it atomically
             (temp file, fsync, rename) on ``close()`` — a partial file
             is never visible.  `Pipeline` fans a reader through a
             transform into a writer; backpressure is structural (both
             hand-off queues are bounded, no stage outruns the others).
             `ReadaheadArray` double-buffers the k-way merge's per-run
             input blocks.  INVARIANT: the pipeline changes only *when*
             bytes move — partitions are bit-identical and the `IOStats`
             sort/scan counters exactly equal with the pipeline on
             (``io_threads>=1``) or off (``io_threads=0``); `IOStats` is
             lock-guarded so producer threads can charge it, while
             wall-clock overlap lives in the separate `AioStats`.
             Exposed as ``io_threads``/``prefetch_depth`` knobs on
             `build_bisim_oocore`, `OocBackend`, and the launcher.

  maintenance.py  §4 out-of-core. `OocBackend` implements the
             `repro.core.maintenance.MaintenanceBackend` storage
             protocol — the contract `BisimMaintainer` programs against:
             a backend owns the graph tables (mutations validate, then
             rewrite), the per-level pid columns (`pid_at`/`set_pid_at`/
             `append_pid_rows` over the build's pid files, accessed as
             windowed sequential merge joins for sorted frontiers), the
             per-level store S (`resolve` = bulk get-or-assign), and the
             topology gathers (`frontier_signatures`, `parents_of`,
             `incident_edges`).  The same update stream over `OocBackend`
             and the in-memory backend yields identical partitions up to
             pid renaming; `IOStats` counters stay linear in k per batch.

Partitions are identical (up to pid renaming) to the in-memory
`repro.core` engines in every signature mode.

Durability & recovery
---------------------
Out-of-core state lives on disk, so a crash mid-write is a first-class
input, not an exception path.  The subsystem's guarantees:

  Checksummed artifacts.  Every persistent `.npy` the engine writes
    (table chunks, pid files, spill runs, WAL records) gets a CRC-32
    over its array data bytes, computed from the in-memory buffer at
    write time — zero extra read I/O.  Checksums live in a versioned
    ``manifest.json`` (`durability.Manifest`) written *last* and
    atomically, so the manifest is the commit point of the whole
    artifact: a torn or bit-flipped file fails `OocGraph.load` /
    snapshot restore with `repro.core.integrity.ChecksumError` instead
    of silently yielding a wrong partition.  Spill runs adopted from a
    snapshot verify lazily on first mmap; runs this process just wrote
    are exempt (we hold the bytes they came from).

  Write-ahead maintenance log.  ``OocBackend(wal=True)`` +
    ``BisimMaintainer(..., wal=True)`` append every mutation (op name +
    argument arrays, `durability.WriteAheadLog`) *before* applying it.
    Records are fsync'd and group-committed (``wal_group`` batches per
    fsync; at most ``group-1`` acknowledged updates can be lost).
    Recovery = `OocBackend.restore(workdir)` (re-opens the last
    `snapshot()` after verifying every checksum) +
    `BisimMaintainer.restore(backend, state)` (replays committed WAL
    records with lsn past the snapshot through the normal maintenance
    methods).  Mid-crash live tables are scratch — recovery never
    reads them.  Cost: O(k·sort(|E_t|) + k·sort(|N_t|)) per replayed
    batch, counted by the backend's `IOStats`.

  Checkpoint/resume builds.  ``build_bisim_oocore(...,
    checkpoint=True)`` writes a per-level ``ckpt.json`` (finished pid
    files + CRCs, iteration stats, `IOStats`, spill-store states);
    ``resume=True`` verifies the finished levels and restarts at the
    first unfinished one with the I/O accounting continuing, not
    restarting.

  Fault injection.  `repro.core.faults.FaultPlan` (installed with
    `install_fault_plan`) deterministically turns the Nth I/O
    fault-point into a crash (`InjectedCrash`), a transient
    (`TransientIOError`, retried with bounded backoff by
    `with_retries`), or a torn write (file published with its tail
    missing — caught later by the checksums).  A transient fault at a
    device step degrades gracefully: the maintainer warns once and falls
    back to the bit-identical numpy path.  Other device errors propagate.

  Non-guarantees.  fsync durability is only as real as the
    filesystem's; uncommitted WAL tail records are dropped (by design);
    the manifest protects artifact *files*, not the free-form workdir
    scratch, which recovery deletes.

Streaming service
-----------------
`service.StreamingMaintenanceService` turns the one-shot batch model
into sustained ingest.  The lifecycle of an op through the service:

  ingest        ``submit(op, arrays)`` appends the record to the WAL
                immediately — that append is the acknowledgement, and
                group commit (``wal_group``, optionally with the fsync
                round running asynchronously on the aio executor via
                ``StreamConfig(async_wal=True)``) bounds the loss
                window to ``group - 1`` acked ops;
  group-commit  records become durable at each group boundary; a
                service stop (`OocBackend.close`) drains in-flight
                async rounds before the executor shuts down, so no
                partial commit line is ever published;
  batch apply   pending ops apply through
                `BisimMaintainer.apply_ops` when the buffer reaches
                ``batch_ops`` or ages past ``batch_deadline_s`` —
                strictly in submission order, so the pid history is
                bit-identical to unbatched application and to WAL
                replay;
  compaction / rebuild cadence
                crossing ``compact_threshold`` (tombstone fraction)
                enqueues a WAL'd ``compact`` op; a §4.2 rebuild fired
                by the maintainer is observed via `on_rebuild` and
                forces an early snapshot;
  snapshot cadence
                every ``snapshot_every`` applied batches the service
                snapshots (WAL commit + manifest-committed snapshot dir
                + truncation; the truncation publishes a durable lsn
                floor first, keeping lsn numbering monotone even across
                a fully truncated log);
  index patch   every ``staleness_batches`` batches the attached
                `repro.quotient.QuotientService` absorbs the
                accumulated changed-node union — one engine epoch per
                absorption, with queries pinned lock-free to the
                pre-patch epoch while it lands.

`StreamingMaintenanceService.recover` resumes a killed stream from the
snapshot + committed WAL; resubmitting the lost suffix reproduces the
never-killed run's pid history bit-identically (``tests/test_stream.py``).

Observability
-------------
Every phase of the subsystem is traced through `repro.obs` — the
zero-dependency tracer whose spans follow the ``layer.phase`` naming
convention (see `repro.obs` for the full taxonomy):

  build.*   per-level pipeline phases of `build_bisim_oocore`
            (``build.level`` / ``build.join`` / ``build.fold`` /
            ``build.rank`` / ``build.pid_write``, each carrying a
            ``level=j`` attribute);
  sort.*    `runs.external_sort` run formation and merge passes
            (``obs_attrs={"level": j}`` threads the level through);
  store.*   `SpillableSigStore` probe/resolve/spill/merge (and the
            ``store.*_device`` variants from `core.device_maint`);
  table.*   `OocGraph` chunk scans (on the aio reader lane when
            prefetch is on) and table rewrites;
  aio.*     pipeline internals — reader/writer thread work plus
            ``aio.wait_read`` / ``aio.wait_write`` consumer stalls, so
            a trace shows exactly where overlap is won or lost;
  wal.*     WAL append/commit (fsync-round latency), replay,
            snapshot and restore;
  maint.*   `BisimMaintainer` propagation (``maint.propagate`` /
            ``maint.level`` / ``maint.rebuild``);
  fault.*   instant events from `core.faults` fault points + retries.

Tracing is OFF by default and contract-neutral: with no tracer
installed each span is a single branch (`obs.NOOP_SPAN`), and enabling
it changes neither partitions nor `IOStats` — asserted by
``tests/test_obs.py``.  Spans carrying ``io=stats`` attach the IOStats
delta accrued inside them as ``io.<field>`` attributes.  The launcher's
``--trace PATH`` writes the Chrome-trace/Perfetto JSON and prints the
aggregated per-phase / per-level `MetricsReport` table.
"""
from .aio import (AioConfig, AioStats, BoundedSaver, Pipeline,
                  PrefetchReader, ReadaheadArray, StreamingWriter)
from .build import OocBisimResult, build_bisim_oocore
from .durability import Manifest, WriteAheadLog
from .maintenance import OocBackend
from .runs import (IOStats, external_sort, lexsort_records, make_records,
                   merge_runs, rebuffer, sort_to_runs)
from .service import (StreamConfig, StreamingMaintenanceService,
                      replay_open_loop, synthesize_ops)
from .tables import ChunkedColumn, OocGraph

__all__ = [
    "OocBisimResult", "build_bisim_oocore", "OocBackend", "IOStats",
    "external_sort", "lexsort_records", "make_records", "merge_runs",
    "rebuffer", "sort_to_runs", "ChunkedColumn", "OocGraph",
    "AioConfig", "AioStats", "BoundedSaver", "Pipeline", "PrefetchReader",
    "ReadaheadArray", "StreamingWriter", "Manifest", "WriteAheadLog",
    "StreamConfig", "StreamingMaintenanceService", "replay_open_loop",
    "synthesize_ops",
]
