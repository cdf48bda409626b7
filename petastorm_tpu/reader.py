"""Reader core: ``make_reader`` / ``make_batch_reader`` / ``Reader``.

Capability parity with petastorm/reader.py (``make_reader`` ~L60, ``make_batch_reader`` ~L200,
``Reader`` ~L330: filtering, sharding, epochs, reset/stop/join, context manager) and the two
worker types (petastorm/py_dict_reader_worker.py ~L40 ``PyDictReaderWorker``,
petastorm/arrow_reader_worker.py ~L60 ``ArrowReaderWorker``), redesigned per SURVEY.md §8:

- Scheduling is a pure deterministic :class:`petastorm_tpu.plan.EpochPlan` (resumable,
  zero-communication multi-host sharding) instead of a ventilator thread.
- Workers return plain python/numpy payloads (no ZeroMQ, no pickled namedtuples); namedtuple
  wrapping happens on the consumer side so results cross process boundaries cheaply.
- The batch path keeps data columnar end-to-end (Arrow → numpy dict) — the layout
  ``petastorm_tpu.loader.DataLoader`` assembles into globally-sharded ``jax.Array`` batches.

``filters`` are applied at two levels (reference ``pq.ParquetDataset`` + ``filters``
semantics, petastorm/reader.py ~L330): hive ``key=value`` partition directories are pruned
from scheduling BEFORE any file is opened (:mod:`petastorm_tpu.partitions`), and the
remaining clauses run as vectorized row-level masks (DNF tuples like pyarrow's) in the
workers. Partition columns materialize as ordinary row/batch values.
"""
from __future__ import annotations

import logging
import os
import random
import re
import threading
import time
import weakref

import numpy as np

from petastorm_tpu import chaos as _chaos
from petastorm_tpu.obs import provenance as _prov
from petastorm_tpu.cache import make_cache
from petastorm_tpu.io import IoOptions
from petastorm_tpu.errors import (
    PERMANENT_IO_ERRORS as _PERMANENT_IO_ERRORS,
    DecodeFieldError,
    NoDataAvailableError,
    PieceRemovedError,
    PieceRewrittenError,
)
from petastorm_tpu.fs import get_filesystem_and_path_or_paths
from petastorm_tpu.metadata import (
    get_schema,
    infer_or_load_unischema,
    load_row_groups,
)
from petastorm_tpu.ngram import NGram
from petastorm_tpu.ops.packing import RaggedColumn
from petastorm_tpu.plan import EpochPlan, shard_indices
from petastorm_tpu.recovery import (
    QuarantinedItem,
    QuarantineEntry,
    QuarantineReport,
    RecoveryOptions,
    count_quarantined,
)
from petastorm_tpu.transform import transform_schema
from petastorm_tpu.unischema import Unischema, UnischemaField
from petastorm_tpu.serializers import SHM_LEASE_KEY as _SHM_LEASE_KEY
from petastorm_tpu.utils import decode_row
from petastorm_tpu.workers import make_executor

logger = logging.getLogger(__name__)


# --------------------------------------------------------------------------------------
# Workers (picklable module-level classes; one instance shared by all pool workers)
# --------------------------------------------------------------------------------------


class _Tagged:
    """Wraps a worker so results carry their (epoch, ordinal) dispatch tag — the bookkeeping
    exact resume needs (picklable for the process pool). Forwards the async-IO
    surface (``prefetch``/``close``/``io_stats``/``set_trace``) so executors and
    pool children talk to the tagged wrapper as if it were the worker."""

    def __init__(self, worker, tenant=None):
        self._worker = worker
        #: resolved TenantContext (ISSUE 18) — pickles into pool children and
        #: read by ProcessExecutor.start to seed the child env (PTPU_TENANT)
        self.tenant_context = tenant

    def __call__(self, tagged_item):
        epoch, ordinal, item = tagged_item
        ctx = self.tenant_context
        if ctx is None:
            return (epoch, ordinal, self._worker(item))
        # activate the tenant around the worker call so every IO charge on
        # this thread (tier bytes, arena admits, hedges) bills the owner, and
        # meter the worker-seconds the item actually consumed
        from petastorm_tpu.obs import tenant as _tenant_mod

        with _tenant_mod.activate(ctx):
            # the executor's begin_item ran BEFORE this activation, so stamp
            # the tenant annotation here — per-tenant attribution folds
            # filter on it
            from petastorm_tpu.obs import provenance as _prov

            _prov.annotate("tenant", ctx.tenant)
            t0 = time.perf_counter()
            try:
                return (epoch, ordinal, self._worker(item))
            finally:
                _tenant_mod.charge("worker_s", time.perf_counter() - t0,
                                   label=ctx.tenant)

    def prefetch(self, tagged_items):
        """Readahead hint: strip the dispatch tags, hand the plan items down."""
        fn = getattr(self._worker, "prefetch", None)
        if fn is not None:
            fn([tagged[2] for tagged in tagged_items])

    def close(self):
        fn = getattr(self._worker, "close", None)
        if fn is not None:
            fn()

    def io_stats(self):
        fn = getattr(self._worker, "io_stats", None)
        return fn() if fn is not None else {}

    def set_trace(self, tracer):
        fn = getattr(self._worker, "set_trace", None)
        if fn is not None:
            fn(tracer)

    def set_health(self, monitor):
        fn = getattr(self._worker, "set_health", None)
        if fn is not None:
            fn(monitor)

    # -- live knob seam (ISSUE 13/14): forwarded so the pool control frame's
    # -- apply_<knob>() dispatch reaches the real worker inside a child

    def apply_readahead_depth(self, depth):
        fn = getattr(self._worker, "apply_readahead_depth", None)
        return fn(depth) if fn is not None else None

    def apply_readahead_bytes(self, nbytes):
        fn = getattr(self._worker, "apply_readahead_bytes", None)
        return fn(nbytes) if fn is not None else None

    def apply_remote_max_inflight(self, max_inflight):
        fn = getattr(self._worker, "apply_remote_max_inflight", None)
        return fn(max_inflight) if fn is not None else None

    def apply_hedge_quantile(self, quantile):
        fn = getattr(self._worker, "apply_hedge_quantile", None)
        return fn(quantile) if fn is not None else None

    def apply_pagedec(self, mode):
        fn = getattr(self._worker, "apply_pagedec", None)
        return fn(mode) if fn is not None else None

    def apply_arena_bytes(self, nbytes):
        fn = getattr(self._worker, "apply_arena_bytes", None)
        return fn(nbytes) if fn is not None else None

    def live_io_knobs(self):
        fn = getattr(self._worker, "live_io_knobs", None)
        return fn() if fn is not None else {}


#: Exception-module roots of the storage client stacks fsspec-bridged filesystems
#: raise through pyarrow (gcsfs.retry.HttpError, botocore errors, aiohttp client
#: errors, google.api_core exceptions, ...). Most of these do NOT derive from
#: OSError, so classification is by origin: an error born in the storage layer is
#: worth the bounded retries — a genuinely permanent one just fails a little later.
_TRANSIENT_ERROR_MODULES = frozenset(
    ("gcsfs", "s3fs", "adlfs", "fsspec", "aiohttp", "aiobotocore", "botocore",
     "urllib3", "requests", "google", "azure"))


def _is_transient_io_error(exc):
    """Retry-worthy? OSErrors are (minus the permanent subclasses); anything raised
    by a storage client stack is; everything else (corrupt parquet → ArrowInvalid,
    user code errors) fails fast."""
    if isinstance(exc, _PERMANENT_IO_ERRORS):
        return False
    if isinstance(exc, OSError):
        return True
    mod = (type(exc).__module__ or "").split(".")[0]
    return mod in _TRANSIENT_ERROR_MODULES


def _close_quietly(pf):
    """Close a cached ParquetFile for real: it wraps an already-open NativeFile
    (``_close_source=False``), so ``close()`` without ``force`` is a no-op and would
    leave the fd/connection to GC."""
    try:
        pf.close(force=True)
    except Exception:  # noqa: BLE001
        pass  # graftlint: disable=GL-O002 (best-effort close of an evicted handle)


def _spec_wants_writable(spec):
    """True when the host transform is an OPAQUE callable that may mutate the
    worker payload in place (the one consumer needing the cache's writable
    escalation). Declarative pipelines are out-of-place by construction."""
    return (spec is not None and not spec.device and spec.func is not None
            and not getattr(spec, "declarative", False))


#: serializes lazy per-process IO-runtime construction (the readahead pool);
#: module-level because worker objects must stay picklable (no instance locks)
_io_init_lock = threading.Lock()

_file_eviction_counter = None


def _count_file_eviction():
    """Bump ``ptpu_io_file_evictions_total`` (resolved once per process)."""
    global _file_eviction_counter
    counter = _file_eviction_counter
    if counter is None:
        from petastorm_tpu.obs.metrics import default_registry

        counter = _file_eviction_counter = default_registry().counter(
            "ptpu_io_file_evictions_total",
            help="cached open-ParquetFile handles closed (LRU bound or "
                 "transient-IO-retry reopen)")
    counter.inc()


class _WorkerBase:
    """Shared row-group loading: column-pruned reads, predicate masking, drop partitions."""

    #: Max cached open parquet files per thread (fd bound: threads × this);
    #: PTPU_MAX_OPEN_FILES overrides for long multi-file epochs on tight ulimits.
    MAX_OPEN_FILES = int(os.environ.get("PTPU_MAX_OPEN_FILES", "") or 64)

    def __init__(self, filesystem, read_schema, stored_schema, predicate, transform_spec,
                 cache, shuffle_row_drop_partitions, filters, seed,
                 device_fields=frozenset(), partition_info=None,
                 io_retries=None, io_retry_backoff_s=None, io_options=None,
                 recovery=None):
        self._fs = filesystem
        self._read_schema = read_schema  # fields to deliver (pre-transform view)
        self._stored_schema = stored_schema  # full stored schema (decode source of truth)
        self._predicate = predicate
        self._transform_spec = transform_spec
        self._cache = cache
        self._drop_partitions = shuffle_row_drop_partitions
        self._filters = filters
        self._seed = seed
        self._device_fields = frozenset(device_fields)  # host-stage-only decode columns
        self._partition_info = partition_info  # hive key=value layout (or None)
        # unified recovery policy (ISSUE 7): the struct is the source of truth;
        # the legacy per-kwarg knobs overlay it when a caller passes them
        self._recovery = RecoveryOptions.resolve(
            recovery, io_retries=io_retries,
            io_retry_backoff_s=io_retry_backoff_s)
        self._io_retries = self._recovery.io_retries
        self._io_retry_backoff_s = self._recovery.io_retry_backoff_s
        self._io_options = IoOptions.normalize(io_options)
        self._local = None  # threading.local built lazily (not picklable)
        self._readahead = None  # ReadaheadPool built lazily per process (threads)
        self._io_closed = False  # latched by close(); reopen() re-arms (reset)
        self._readahead_unavailable = False  # this worker's pool failed to build
        self._io_tracer = None
        self._io_health = None  # optional HealthMonitor for the IO threads
        self._remote = None  # RemoteReadEngine built lazily per process (ISSUE 8)
        self._remote_unavailable = False  # this worker's engine failed to build
        #: live knob overrides (ISSUE 13): applied retunes recorded here so a
        #: LAZILY-built pool/engine starts at the retuned value (and a pool
        #: child spawned after a retune inherits it through the pickle); the
        #: IoOptions struct itself is never mutated (graftlint GL-C004)
        self._knob_overrides = {}
        #: pass-through negative memo (ISSUE 14): (path, column) pairs whose
        #: chunks declined at the PAGE level (no byte saving / unsupported
        #: encoding) — footer eligibility would otherwise re-fetch the raw
        #: span on every read just to decline again. Conservative by design
        #: (one declining chunk mutes the column for the whole file);
        #: invalidate_pieces clears the path's entries on a rewrite.
        self._pagedec_refused = set()

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_local"] = None
        state["_readahead"] = None  # each pool child builds its own IO runtime
        state["_io_closed"] = False
        state["_readahead_unavailable"] = False  # a child retries its own build
        state["_io_tracer"] = None
        state["_io_health"] = None  # owns threads — never crosses the pickle wire
        state["_remote"] = None  # each pool child builds its own GET pool
        state["_remote_unavailable"] = False
        return state

    def _cache_get(self, key, fill):
        """Cache read under the lease contract (ISSUE 6): a lease-aware cache
        (``MemCache``) serves zero-copy READ-ONLY views by default, but an
        OPAQUE host ``TransformSpec`` runs user code against the returned
        payload (pandas frames / row dicts aliasing the cached arrays) that
        may legitimately mutate in place — that consumer "actually writes",
        so the worker escalates to the cache's copy-on-write path up front
        and the copy is charged to the census (``memcache_cow``).

        Declarative :class:`~petastorm_tpu.ops.tabular.FeaturePipeline`
        transforms never mutate delivered payloads in place (each fused stage
        materializes its own output column), so they keep the zero-copy
        read-only serving contract — the ISSUE-9 narrowing of the
        writable-batch request."""
        writable = _spec_wants_writable(self._transform_spec)
        get_writable = getattr(self._cache, "get_writable", None)
        if writable and get_writable is not None:
            return get_writable(key, fill)
        return self._cache.get(key, fill)

    def _footer_cache(self):
        """The process-wide parsed-footer cache (ISSUE 8), or ``None`` when
        ``remote.footer_cache_bytes`` disables it. Shared by every worker
        thread AND the planner's footer scan — each file's footer is read and
        parsed once per process instead of once per thread."""
        budget = self._io_options.remote.footer_cache_bytes
        if not budget:
            return None
        from petastorm_tpu.io.footercache import configure_budget

        return configure_budget(budget)

    def _parquet_file(self, path):
        import pyarrow.parquet as pq

        if self._local is None:
            self._local = threading.local()
        cache = getattr(self._local, "files", None)
        if cache is None:
            from collections import OrderedDict

            cache = self._local.files = OrderedDict()
        pf = cache.get(path)
        if pf is None:
            f = self._fs.open_input_file(path)
            footers = self._footer_cache()
            metadata = None
            if footers is not None:
                # hit: pq.ParquetFile(metadata=...) opens with ZERO footer
                # reads; miss: the parse below populates the shared cache (the
                # handle's size() doubles as the entry's validation token)
                metadata = footers.get(self._fs, path, source=f).metadata
            pf = cache[path] = pq.ParquetFile(f, metadata=metadata)
            # the open handle doubles as the pass-through path's raw-span
            # reader (ISSUE 14): positional read_at calls never disturb the
            # ParquetFile's own cursor
            pf._ptpu_source = f
            while len(cache) > self.MAX_OPEN_FILES:  # LRU-evict to bound open fds
                _, old = cache.popitem(last=False)
                _close_quietly(old)
                _count_file_eviction()
        else:
            cache.move_to_end(path)
        return pf

    # -- mutable-dataset generation enforcement (ISSUE 11) ------------------------------

    def _verify_generation(self, piece):
        """Validate the piece's stamped generation token against the file as
        it exists NOW (dataset watching on — ``piece.generation`` stamped).

        A vanished file raises :class:`PieceRemovedError`; a stat or
        footer-crc mismatch invalidates the piece's footer/open-handle/cache
        entries and raises :class:`PieceRewrittenError` — both permanent
        (never burned as transient retries), both quarantinable under the
        PR-7 policy with their own causes. The hard invariant this enforces:
        a read can only ever deliver rows of the generation stamped into its
        plan item, so one epoch never mixes two generations of one file."""
        from petastorm_tpu.dataset.watch import current_stat_token, stat_token_of

        stat = current_stat_token(self._fs, piece.path)  # raises PieceRemovedError
        stamped_crc = piece.generation.rsplit(".", 1)[1]
        mismatch = stat_token_of(piece.generation) != stat
        if not mismatch and stamped_crc != "-":
            # stat identity held — a size+mtime-colliding rewrite can still
            # hide behind it; the footer crc (pinned to THIS stat identity,
            # so a stale parse cannot vouch for the new bytes) settles it
            from petastorm_tpu.io.footercache import shared_footer_cache

            entry = shared_footer_cache().get(self._fs, piece.path,
                                              stat_token=stat)
            mismatch = ("%08x" % entry.crc) != stamped_crc
        if mismatch:
            # NOT counted here: this runs in the worker — a pool child's
            # registry is invisible to the parent's export. The parent counts
            # ptpu_dataset_generation_conflicts_total when it absorbs the
            # piece_rewritten quarantine marker (Reader._absorb_quarantine).
            self.invalidate_pieces([piece])
            raise PieceRewrittenError(
                "%s row group %d was rewritten under the running reader "
                "(stamped generation %s no longer matches the file); its "
                "cache entries are invalidated and the watcher re-plans the "
                "new generation into a later epoch"
                % (piece.path, piece.row_group, piece.generation))

    def invalidate_pieces(self, pieces):
        """Drop every cache layer's entries for ``pieces`` under their
        stamped generation: the open-handle LRU + shared footer entry, and
        the mem/disk tiers' decoded payloads by exact key. Called by the
        read path on a generation conflict and by the dataset watcher when
        it observes a removal/rewrite."""
        invalidate = getattr(self._cache, "invalidate", None)
        for piece in pieces:
            self._evict_parquet_file(piece.path)
            # a rewritten file may compress differently: let the pass-through
            # re-judge its columns from the fresh bytes
            self._pagedec_refused = {
                t for t in self._pagedec_refused if t[0] != piece.path}
            if invalidate is not None:
                for partition in range(max(1, self._drop_partitions)):
                    invalidate(_cache_key(
                        piece, self._read_schema, self._predicate,
                        self._filters, partition, self._drop_partitions,
                        self._seed, self._device_fields))

    def _evict_parquet_file(self, path):
        """Drop (and close) the cached handle for ``path`` — a transient IO failure may
        leave it holding a dead connection; the retry must reopen from scratch.
        The shared footer entry is invalidated too: if the failure was the
        file being replaced, a retry replanning ranged GETs from the stale
        footer's offsets would fail identically forever."""
        cache = getattr(self._local, "files", None) if self._local is not None else None
        if cache is not None:
            pf = cache.pop(path, None)
            if pf is not None:
                _close_quietly(pf)
                _count_file_eviction()
        footers = self._footer_cache()
        if footers is not None:
            footers.invalidate(path)

    # -- async read path (ISSUE 4) ------------------------------------------------------

    def _readahead_pool(self, create=False):
        """The per-process readahead pool (None when the feature is off).

        Built lazily on the first ``prefetch`` — never pickled (each pool child
        constructs its own), never built by foreground reads (a reader whose
        executor sends no hints stays fully synchronous). A construction
        failure degrades the feature off for this worker with a logged
        ``readahead_unavailable`` cause."""
        if not self._io_options.readahead or self._readahead_unavailable:
            return None
        pool = self._readahead
        if pool is None and create:
            with _io_init_lock:
                pool = self._readahead
                if pool is None and not self._io_closed:
                    from petastorm_tpu.io.readahead import ReadaheadPool

                    opts = self._io_options
                    try:
                        # byte-gap run merging only under object-store request
                        # economics (remote tier active): local reads keep the
                        # PR 4 strict-adjacency behavior
                        gap_ok = self._rowgroup_gap_ok \
                            if opts.remote.active_for(self._fs) else None
                        knobs = self._knob_overrides
                        pool = ReadaheadPool(
                            self._read_columns_sync, read_run_fn=self._read_run,
                            depth=knobs.get("readahead_depth",
                                            opts.readahead_depth),
                            byte_budget=knobs.get("readahead_bytes",
                                                  opts.readahead_bytes),
                            io_threads=knobs.get("io_threads", opts.io_threads),
                            coalesce=opts.coalesce,
                            coalesce_max_run=opts.coalesce_max_run,
                            gap_ok=gap_ok)
                    except Exception as e:  # noqa: BLE001 — degrade to sync reads
                        from petastorm_tpu.obs.log import degradation

                        degradation(
                            "readahead_unavailable",
                            "readahead pool construction failed (%s); reads stay "
                            "synchronous", e)
                        # per-WORKER flag, never the caller-owned IoOptions: one
                        # IoOptions may be shared across readers, and one
                        # worker's failure must not flip the feature off there
                        self._readahead_unavailable = True
                        return None
                    if self._io_tracer is not None:
                        pool.set_trace(self._io_tracer)
                    if self._io_health is not None:
                        pool.set_health(self._io_health)
                    self._readahead = pool
        return pool

    # -- remote tier (ISSUE 8) ----------------------------------------------------------

    def _remote_engine(self, create=False):
        """The per-process ranged-GET engine, or ``None`` when the remote
        tier is off for this filesystem (local reads keep the classic
        ``ParquetFile`` path untouched). Built lazily like the readahead
        pool — never pickled, each pool child constructs its own; a
        construction failure degrades this worker to classic reads with a
        logged ``remote_unavailable`` cause."""
        if self._remote_unavailable:
            return None
        engine = self._remote
        if engine is None and create:
            opts = self._io_options.remote
            if not opts.active_for(self._fs):
                self._remote_unavailable = True  # cheap latch: probe once
                return None
            with _io_init_lock:
                engine = self._remote
                if engine is None and not self._io_closed:
                    try:
                        from petastorm_tpu.io.remote import build_engine

                        engine = build_engine(self._fs, opts)
                    except Exception as e:  # noqa: BLE001 — degrade to classic reads
                        from petastorm_tpu.obs.log import degradation

                        degradation(
                            "remote_unavailable",
                            "remote ranged-GET engine construction failed "
                            "(%s); reads use the classic ParquetFile path", e)
                        self._remote_unavailable = True
                        return None
                    if engine is None:
                        self._remote_unavailable = True
                        return None
                    # live retunes applied before this lazy build (ISSUE 13):
                    # the fresh engine starts at the retuned values
                    knobs = self._knob_overrides
                    if "remote_max_inflight" in knobs:
                        engine.apply_max_inflight(knobs["remote_max_inflight"])
                    if "hedge_quantile" in knobs:
                        engine.apply_hedge_quantile(knobs["hedge_quantile"])
                    self._remote = engine
        return engine

    def _rowgroup_gap_ok(self, prev, piece):
        """Byte-gap predicate for non-adjacent run coalescing: True when the
        hole between two row groups of one file (footer-cache spans) is at
        most the remote tier's ``min_gap_bytes`` — reading it is cheaper
        than a second round trip. Conservative ``False`` when the footer is
        not cached yet."""
        footers = self._footer_cache()
        if footers is None:
            return False
        entry = footers.peek(prev.path)
        if entry is None or piece.row_group >= entry.num_row_groups \
                or prev.row_group >= entry.num_row_groups:
            return False  # stale/foreign footer: never index past its groups
        gap = entry.row_group_span(piece.row_group)[0] \
            - entry.row_group_span(prev.row_group)[1]
        return 0 <= gap <= self._io_options.remote.min_gap_bytes

    def prefetch(self, items):
        """Dispatch lookahead hint: issue background reads for the upcoming plan
        ``items`` (``(piece, partition)`` tuples) so IO overlaps the current
        item's decode. Never raises — a scheduling failure degrades to
        synchronous reads with a logged cause."""
        pool = self._readahead_pool(create=True)
        if pool is None or not items:
            return
        try:
            columns = self._first_read_columns()
            requests = []
            for item in items:
                piece, partition = item
                if self._cache_contains(piece, partition):
                    continue  # the (mem/disk) cache will serve it without a read
                cols = columns
                # pass-through columns (ISSUE 14) are fetched by the
                # foreground read as raw pages — prefetching their DECODED
                # form would read them twice and key-miss besides. peek_only:
                # a prefetch never pays a footer fetch; until the footer is
                # cached the hint simply requests the full (classic) set.
                eligible = self._pagedec_eligible(piece, columns,
                                                  peek_only=True)
                if eligible:
                    cols = [c for c in columns if c not in eligible]
                    if not cols:
                        continue  # nothing classic left to prefetch
                requests.append((piece, cols))
            if requests:
                pool.schedule(requests)
        except Exception as e:  # noqa: BLE001 — prefetch must never fail a read
            from petastorm_tpu.obs.log import degradation

            degradation("readahead_fallback",
                        "prefetch scheduling failed (%s); reads stay synchronous", e)

    def _cache_contains(self, piece, partition):
        key = _cache_key(piece, self._read_schema, self._predicate, self._filters,
                         partition, self._drop_partitions, self._seed,
                         self._device_fields)
        return self._cache.contains(key)

    def _first_read_columns(self):
        """The column selection of this worker's FIRST read for any piece — what
        the readahead must request for its prefetched table to be a hit."""
        raise NotImplementedError

    def close(self):
        """Release the per-process IO runtime (Reader.join / pool-child exit)
        and latch prefetching off — a straggling executor thread mid-loop must
        not rebuild the pool under a teardown. Idempotent; :meth:`reopen`
        (Reader restart) re-arms it."""
        with _io_init_lock:
            self._io_closed = True
            pool, self._readahead = self._readahead, None
            engine, self._remote = self._remote, None
        if pool is not None:
            pool.shutdown()
        if engine is not None:
            engine.shutdown()

    def reopen(self):
        """Re-arm lazy readahead/remote-engine construction after a
        :meth:`close` (the Reader calls this from ``_start`` so ``reset()``
        gets a fresh IO runtime)."""
        with _io_init_lock:
            self._io_closed = False

    def io_stats(self):
        """Live async-IO gauges: readahead + cache tiers + remote engine +
        footer cache (empty dicts when off). Surfaced through
        ``Reader.io_stats()`` for thread/dummy pools."""
        out = {}
        pool = self._readahead
        if pool is not None:
            out.update(pool.stats())
        stats_fn = getattr(self._cache, "stats", None)
        if stats_fn is not None:
            out.update(stats_fn())
        engine = self._remote
        if engine is not None:
            out.update(engine.stats())
        footers = self._footer_cache()
        if footers is not None:
            out.update(footers.stats())
        from petastorm_tpu.io import arena as _arena_mod

        arena_obj = _arena_mod.process_arena()
        if arena_obj is not None:
            out.update(arena_obj.stats())
        return out

    def set_trace(self, tracer):
        self._io_tracer = tracer
        pool = self._readahead
        if pool is not None:
            pool.set_trace(tracer)

    def set_health(self, monitor):
        self._io_health = monitor
        pool = self._readahead
        if pool is not None:
            pool.set_health(monitor)

    # -- live knobs (ISSUE 13) ----------------------------------------------------------
    #
    # The sanctioned retune seam the controller's KnobSet binds to. Each
    # apply records the override (a lazily-built pool/engine starts retuned;
    # pool children spawned after the retune inherit it through the pickle)
    # and forwards to the live component when one exists. The IoOptions
    # struct is never mutated (GL-C004): one options object may be shared
    # across readers, and a retune here must stay this reader's.

    def live_io_knobs(self):
        """The LIVE IO knob values (overrides > live components > options)."""
        opts = self._io_options
        pool = self._readahead
        engine = self._remote
        knobs = self._knob_overrides
        return {
            "readahead_depth": pool.depth if pool is not None
            else knobs.get("readahead_depth", opts.readahead_depth),
            "readahead_bytes": (pool.byte_budget or 0) if pool is not None
            else knobs.get("readahead_bytes", opts.readahead_bytes),
            "io_threads": pool.io_threads if pool is not None
            else knobs.get("io_threads", opts.io_threads),
            "remote_max_inflight": engine.max_inflight if engine is not None
            else knobs.get("remote_max_inflight", opts.remote.max_inflight),
            "hedge_quantile": engine.hedge_quantile if engine is not None
            else knobs.get("hedge_quantile", opts.remote.hedge_quantile),
        }

    def apply_readahead_depth(self, depth):
        """Retune the prefetch window live. The IO thread pool is sized with
        it (bounded) — a deeper window on the configured 2 threads would
        queue, not overlap."""
        depth = max(1, int(depth))
        self._knob_overrides["readahead_depth"] = depth
        io_threads = max(self._io_options.io_threads, min(depth, 16))
        self._knob_overrides["io_threads"] = io_threads
        pool = self._readahead
        if pool is not None:
            pool.apply_depth(depth)
            pool.apply_io_threads(io_threads)
        return depth

    def apply_readahead_bytes(self, nbytes):
        nbytes = max(0, int(nbytes))
        self._knob_overrides["readahead_bytes"] = nbytes
        pool = self._readahead
        if pool is not None:
            pool.apply_byte_budget(nbytes)
        return nbytes

    def apply_remote_max_inflight(self, max_inflight):
        max_inflight = max(1, int(max_inflight))
        self._knob_overrides["remote_max_inflight"] = max_inflight
        engine = self._remote
        if engine is not None:
            engine.apply_max_inflight(max_inflight)
        return max_inflight

    def apply_hedge_quantile(self, quantile):
        quantile = min(0.999, max(0.5, float(quantile)))
        self._knob_overrides["hedge_quantile"] = quantile
        engine = self._remote
        if engine is not None:
            engine.apply_hedge_quantile(quantile)
        return quantile

    def apply_mem_cache_bytes(self, nbytes):
        """Retune the mem tier's budget (the hot-row-group promotion lever);
        a no-op returning 0 when no mem tier exists."""
        mem = getattr(self._cache, "mem", None)
        if mem is None:
            return 0
        return mem.apply_budget(nbytes)

    def apply_arena_bytes(self, nbytes):
        """Retune the host-wide arena budget (ISSUE 17). The budget lives in
        the shared control segment, so one actuation — wherever it lands —
        governs every attached process's admissions; shrinking evicts unheld
        entries host-wide immediately. No-op returning 0 without an arena."""
        from petastorm_tpu.io import arena as _arena_mod

        arena_obj = _arena_mod.process_arena()
        if arena_obj is None:
            return 0
        return arena_obj.set_budget(nbytes)

    # -- compressed-page pass-through (ISSUE 14) ----------------------------------------
    #
    # Eligible fixed-width columns skip pyarrow's host inflate entirely: the
    # raw compressed pages ride the delivery path as opaque
    # PassthroughColumn values and inflate on device in the loader
    # (ops/pagedec_kernels.py). Ineligible columns degrade PER COLUMN to the
    # classic read (cause=pagedec_ineligible, warn-once), so any dataset
    # works unchanged. The whole mode is the IoOptions.pagedec auto/on/off
    # knob — also a live enum Knob the controller can flip back to host
    # inflate (apply_pagedec below).

    #: per-row workers decode rows — pass-through is a batch-path feature
    _pagedec_supported = False

    def live_pagedec(self):
        """The LIVE pagedec mode (override > options) — the knob getter."""
        return self._knob_overrides.get("pagedec", self._io_options.pagedec)

    def apply_pagedec(self, mode):
        """Retune the pass-through mode live (lands on the next read; for
        process pools the retune rides the pool control frame)."""
        mode = str(mode).strip().lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError("pagedec accepts auto/on/off, got %r" % mode)
        self._knob_overrides["pagedec"] = mode
        return mode

    def _pagedec_active(self):
        """Is pass-through live for this worker's reads? ``on`` forces it
        (the pool wire ships compressed either way). ``auto`` stays off: on
        a CPU host inflate is strictly cheaper, and on an accelerator the
        device inflate is not yet measured faster than the host's (PR 21),
        so there it says so once under ``pagedec_auto_off``. Pool children
        never pay a jax import for the probe."""
        if not self._pagedec_supported:
            return False
        mode = self.live_pagedec()
        if mode == "on":
            return True
        if mode == "auto":
            import sys

            jax = sys.modules.get("jax")
            # a backend that fails to start raises here: it is not "no device"
            if jax is not None and jax.default_backend() != "cpu":
                from petastorm_tpu.obs.log import degradation

                degradation(
                    "pagedec_auto_off", "pagedec='auto' keeps host inflate on "
                    "%s: the device inflate is not measured faster; "
                    "pass pagedec='on' to ship compressed pages",
                    jax.default_backend())
        return False

    def _pagedec_shape_ok(self):
        """Row-selecting features (predicate/filter masks, row-drop
        partitions) and in-worker rewrites (host transforms, NGram windows)
        need decoded rows — the whole read falls back when any is
        configured."""
        return (self._predicate is None and not self._filters
                and self._drop_partitions <= 1
                and self._transform_spec is None
                and getattr(self, "_ngram", None) is None)

    def _pagedec_footer(self, path, peek_only=False):
        """The parsed footer for eligibility classification, or ``None``.
        ``peek_only`` (the prefetch path) never triggers IO — eligibility is
        then simply unknown until the first real open caches the footer."""
        footers = self._footer_cache()
        if footers is not None:
            entry = footers.peek(path)
            if entry is not None:
                return entry.metadata
        if peek_only:
            return None
        engine = self._remote_engine(create=True)
        try:
            if engine is not None:
                return engine.footer(path).metadata
            return self._parquet_file(path).metadata
        except Exception:  # noqa: BLE001 — the classic read will surface it
            return None

    def _pagedec_eligible(self, piece, wanted, peek_only=False):
        """Footer-only eligibility: ``{column: (col_index, Eligibility)}``
        for the eligible subset of ``wanted``. Cheap (no chunk bytes); the
        walker's page-level gate runs after the raw spans arrive."""
        if not self._pagedec_active() or not self._pagedec_shape_ok():
            return {}
        md = self._pagedec_footer(piece.path, peek_only=peek_only)
        if md is None or piece.row_group >= md.num_row_groups:
            return {}
        from petastorm_tpu.io.pagedec import classify_chunk

        names = set(wanted)
        out = {}
        rgmd = md.row_group(piece.row_group)
        for i in range(rgmd.num_columns):
            name = rgmd.column(i).path_in_schema.split(".")[0]
            if name not in names or name in out \
                    or (piece.path, name) in self._pagedec_refused:
                continue
            el = classify_chunk(md, piece.row_group, i)
            if el.eligible:
                out[name] = (i, el)
        return out

    def _pagedec_read(self, piece, eligible):
        """Fetch + walk the eligible columns' raw chunk spans into
        PassthroughColumn values (``io.pagedec`` span + chaos hook site).
        Page-level ineligibility degrades per column; corruption raises
        :class:`~petastorm_tpu.errors.PagedecCorruptError` (permanent,
        quarantine-eligible)."""
        from petastorm_tpu.io.pagedec import (PassthroughColumn, build_chunk,
                                              chunk_byte_range,
                                              pagedec_counters,
                                              shared_page_index)
        from petastorm_tpu.obs.log import degradation

        out = {}
        counters = pagedec_counters()
        with _prov.span("io.pagedec"):
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.hit("io.pagedec",
                                  key="%s:%s" % (piece.path, piece.row_group))
            md = self._pagedec_footer(piece.path)
            if md is None:
                # the footer vanished between eligibility and read (cache
                # eviction + a failing re-fetch): degrade to the classic
                # read, whose own path surfaces/classifies the real error
                return {}
            raws = self._pagedec_fetch_raw(piece, eligible)
            rgmd = md.row_group(piece.row_group)
            for name, (col_idx, el) in eligible.items():
                raw = raws.get(name)
                if raw is None:
                    continue
                chunk, reason = build_chunk(raw, el,
                                            expected_values=rgmd.num_rows)
                if chunk is None:
                    counters["fallback_columns"].inc()
                    # mute the column for this file: re-fetching its raw span
                    # on every read just to decline again is pure overhead
                    self._pagedec_refused.add((piece.path, name))
                    degradation(
                        "pagedec_ineligible",
                        "column %r of %s degraded to the classic host-inflate "
                        "path (%s); further per-column fallbacks are counted "
                        "in ptpu_pagedec_fallback_columns_total",
                        name, piece.path, reason)
                    continue
                start, _length = chunk_byte_range(rgmd.column(col_idx))
                shared_page_index().put(
                    piece.path, piece.row_group, name, start,
                    [start + p.header_offset
                     for p in ((chunk.dict_page,) if chunk.dict_page else ())
                     + chunk.pages])
                out[name] = PassthroughColumn.from_chunk(chunk)
        return out

    def _pagedec_fetch_raw(self, piece, eligible):
        """Raw chunk byte spans for the eligible columns: ONE batched
        ranged-GET plan through the remote engine (page-granular splits on
        re-reads), or positional reads on the local open handle."""
        engine = self._remote_engine(create=True)
        if engine is not None:
            return engine.read_raw_column_chunks(
                piece.path, piece.row_group, list(eligible))
        from petastorm_tpu.io.pagedec import chunk_byte_range

        pf = self._parquet_file(piece.path)
        source = getattr(pf, "_ptpu_source", None)
        md = pf.metadata
        rgmd = md.row_group(piece.row_group)
        out = {}
        for name, (col_idx, _el) in eligible.items():
            start, length = chunk_byte_range(rgmd.column(col_idx))
            if source is not None:
                out[name] = bytes(source.read_at(length, start))
            else:
                with self._fs.open_input_file(piece.path) as f:
                    out[name] = bytes(f.read_at(length, start))
        return out

    # -- reads --------------------------------------------------------------------------

    def _read_columns(self, piece, columns):
        """Read a row group restricted to ``columns`` (None = all), serving from
        the readahead pool when the dispatch layer prefetched it (the pool's
        failure semantics mirror the synchronous retry path — see
        petastorm_tpu/io/readahead.py)."""
        pool = self._readahead_pool()
        if pool is not None:
            table = pool.get(piece, columns)
            if table is not None:
                return table
            # a readahead MISS falling to the blocking path is EXPOSED read
            # latency just like a foreground wait — the controller's
            # grow-readahead trigger scale (io/readahead.py stats)
            t0 = time.perf_counter()
            table = self._read_columns_sync(piece, columns)
            pool.note_sync_read(time.perf_counter() - t0)
            return table
        return self._read_columns_sync(piece, columns)

    def _read_columns_sync(self, piece, columns):
        """The blocking read with transient-IO retry. Hive partition columns
        (directory values, not in the file) are appended as constants.

        Transient IO errors (connection resets, timeouts — routine against object
        stores at pod scale) are retried up to ``io_retries`` times with jittered
        exponential backoff, reopening the file each time. The reference has no retry
        anywhere (SURVEY.md §6: a worker exception kills the read); permanent
        conditions (missing file, bad permissions) still fail fast."""
        return self._retry_io(
            lambda: self._read_columns_once(piece, columns), piece.path,
            "%s row group %d" % (piece.path, piece.row_group))

    def _retry_io(self, fn, path, what):
        """One copy of the transient-retry protocol, shared by single-row-group
        and coalesced ranged reads (identical budget either way). Policy comes
        from :class:`~petastorm_tpu.recovery.RecoveryOptions`: ``io_retries``
        extra attempts, jittered exponential backoff capped at
        ``io_retry_max_backoff_s``, and an optional ``read_deadline_s`` wall
        cap across ALL attempts of one read. Every retry is routed through the
        degradation log as ``cause=io_retry`` (counted per occurrence,
        warn-once logging) so a retry storm is visible in
        ``petastorm-tpu-stats`` and the flight record instead of scrolling by
        as ad-hoc warnings."""
        rec = self._recovery
        attempt = 0
        t_first = time.monotonic()
        while True:
            try:
                return fn()
            except Exception as e:  # noqa: BLE001 — classified below
                if not _is_transient_io_error(e) or attempt >= rec.io_retries:
                    raise
                if rec.read_deadline_s and \
                        time.monotonic() - t_first >= rec.read_deadline_s:
                    from petastorm_tpu.obs.log import degradation

                    degradation(
                        "io_retry",
                        "read deadline (%.0fs) exhausted for %s after %d "
                        "attempt(s); raising the last error", rec.read_deadline_s,
                        what, attempt + 1)
                    raise
                self._evict_parquet_file(path)
                _prov.annotate_add("io_retries", 1)
                delay = min(
                    rec.io_retry_backoff_s * (2 ** attempt) * (0.5 + random.random()),
                    rec.io_retry_max_backoff_s)
                from petastorm_tpu.obs.log import degradation

                degradation(
                    "io_retry",
                    "Transient IO error reading %s (%s); retry %d/%d in %.2fs",
                    what, e, attempt + 1, rec.io_retries, delay)
                time.sleep(delay)
                attempt += 1

    def _read_columns_once(self, piece, columns):
        with _prov.span("reader.read"):
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.hit("reader.read",
                                  key="%s:%s" % (piece.path, piece.row_group))
            if getattr(piece, "generation", None) is not None:
                self._verify_generation(piece)
            engine = self._remote_engine(create=True)
            if engine is not None:
                # the engine filters unavailable columns against the footer it
                # already resolved — one metadata fetch per read, not two
                table, _ = engine.read_row_groups(piece.path,
                                                  [piece.row_group], columns)
                return self._attach_partitions(table, piece, columns)
            pf = self._parquet_file(piece.path)
            available = set(pf.schema_arrow.names)
            file_columns = columns
            if columns is not None:
                file_columns = [c for c in columns if c in available]
            table = pf.read_row_group(piece.row_group, columns=file_columns)
            return self._attach_partitions(table, piece, columns)

    def _attach_partitions(self, table, piece, columns):
        if self._partition_info:
            from petastorm_tpu.partitions import attach_partition_columns

            table = attach_partition_columns(
                table, piece, self._partition_info,
                wanted=None if columns is None else set(columns))
        return table

    def _read_run(self, pieces, columns):
        """Coalesced ranged read: adjacent row groups of ONE file in a single
        ``read_row_groups`` call, sliced back into per-piece tables (the
        readahead pool's ``read_run_fn``; byte-identical to per-group reads —
        `petastorm-tpu-bench io --smoke` asserts it in CI)."""
        return self._retry_io(
            lambda: self._read_run_once(pieces, columns), pieces[0].path,
            "%s row groups %s" % (pieces[0].path,
                                  [p.row_group for p in pieces]))

    def _read_run_once(self, pieces, columns):
        from petastorm_tpu.io.coalesce import split_run_table

        with _prov.span("reader.read_run"):
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.hit(
                    "reader.read_run",
                    key="%s:%s" % (pieces[0].path,
                                   ",".join(str(p.row_group) for p in pieces)))
            if getattr(pieces[0], "generation", None) is not None:
                self._verify_generation(pieces[0])  # one file per run
            row_groups = [p.row_group for p in pieces]
            engine = self._remote_engine(create=True)
            if engine is not None:
                table, entry = engine.read_row_groups(pieces[0].path,
                                                      row_groups, columns)
                sizes = [entry.row_group_rows[rg] for rg in row_groups]
                return [self._attach_partitions(t, piece, columns)
                        for t, piece in zip(split_run_table(table, sizes),
                                            pieces)]
            pf = self._parquet_file(pieces[0].path)
            available = set(pf.schema_arrow.names)
            file_columns = columns
            if columns is not None:
                file_columns = [c for c in columns if c in available]
            table = pf.read_row_groups(row_groups, columns=file_columns)
            sizes = [pf.metadata.row_group(rg).num_rows for rg in row_groups]
            return [self._attach_partitions(t, piece, columns)
                    for t, piece in zip(split_run_table(table, sizes), pieces)]

    def _row_mask(self, table):
        """Boolean keep-mask from filters + predicate over a row-group table (or None)."""
        mask = None
        if self._filters:
            mask = _dnf_mask(table, self._filters)
        if self._predicate is not None:
            cols = {
                name: _column_to_numpy(table, name, self._stored_schema)
                for name in self._predicate.get_fields()
            }
            pmask = np.asarray(self._predicate.do_include_vectorized(cols), dtype=bool)
            mask = pmask if mask is None else (mask & pmask)
        return mask

    def _drop_partition_indices(self, piece, num_rows):
        """Deterministic 1/k row subset for shuffle_row_drop_partitions (reference
        petastorm/reader.py ~L520 + worker ``_read_with_shuffle_row_drop``).

        Seeded with crc32(path) — NOT hash(), which is PYTHONHASHSEED-randomized per
        interpreter and would make partitions computed in different pool processes neither
        tile nor cover the row group."""
        import zlib

        piece_key, partition = piece
        k = self._drop_partitions
        seq = np.random.SeedSequence(
            [0 if self._seed is None else int(self._seed),
             zlib.crc32(piece_key.path.encode("utf-8")) & 0x7FFFFFFF,
             piece_key.row_group]
        )
        perm = np.random.Generator(np.random.PCG64(seq)).permutation(num_rows)
        return np.sort(np.array_split(perm, k)[partition])


class PyDictWorker(_WorkerBase):
    """Per-row decode path (reference ``PyDictReaderWorker``): row group → decoded row dicts.

    Predicate IO saving is kept: predicate columns are read and masked first; remaining columns
    are fetched only when some rows match. NGram windows are assembled in-worker — after the
    TransformSpec runs, against the post-transform schema (``ngram_schema``), matching the
    downstream namedtuple views.
    """

    def __init__(self, *args, ngram=None, ngram_schema=None, **kwargs):
        super().__init__(*args, **kwargs)
        self._ngram = ngram
        self._ngram_schema = ngram_schema

    def __call__(self, item):
        piece, _partition = item
        cache_key = _cache_key(piece, self._read_schema, self._predicate, self._filters,
                               item[1], self._drop_partitions, self._seed,
                               self._device_fields)
        rows = self._cache_get(cache_key, lambda: self._load_rows(item))
        spec = self._transform_spec
        if spec is not None and not spec.device:
            with _prov.span("transform"):
                if getattr(spec, "declarative", False):
                    # compiled declarative pipeline: ONE columnar application
                    # over the whole row group (and thus over each NGram
                    # window's columnar form) instead of a func(dict(r)) per row
                    rows = spec.apply_rows(rows)
                elif spec.func is not None:
                    rows = [spec.func(dict(r)) for r in rows]
        if self._ngram is not None:
            # sort/window on decoded (and transformed) rows; plain dicts for cheap IPC
            return self._form_ngram_dicts(rows)
        return rows

    def _mask_fields(self):
        """Sorted predicate+filter columns — the head read's selection when a
        row mask runs first (empty list = no mask read)."""
        predicate_fields = sorted(self._predicate.get_fields()) if self._predicate else []
        filter_fields = sorted(_dnf_fields(self._filters)) if self._filters else []
        return sorted(set(predicate_fields) | set(filter_fields))

    def _first_read_columns(self):
        # EXACTLY the head read of _load_rows (same _mask_fields source, so the
        # two cannot drift): predicate/filter columns when a mask runs first
        # (IO saving kept), the full wanted set otherwise — a prefetched table
        # is keyed by this list and must match to hit
        return self._mask_fields() or list(self._read_schema.fields.keys())

    def _load_rows(self, item):
        piece, partition = item
        wanted = list(self._read_schema.fields.keys())
        first_pass = self._mask_fields() or None

        if first_pass is not None:
            head = self._read_columns(piece, first_pass)
            mask = self._row_mask(head)
            if mask is not None and not mask.any():
                return []
            # second pass fetches only the columns the head read didn't already
            # decode — straight to the sync path: this key is never prefetched,
            # and routing it through the pool would just count a bogus miss
            remaining = sorted(set(wanted) - set(head.column_names))
            if remaining:
                tail = self._read_columns_sync(piece, remaining)
                table = _merge_tables(head, tail)
            else:
                table = head
        else:
            mask = None
            table = self._read_columns(piece, wanted)

        indices = np.arange(table.num_rows)
        if mask is not None:
            indices = indices[mask]
        if self._drop_partitions > 1:
            keep = self._drop_partition_indices(item, table.num_rows)
            indices = np.intersect1d(indices, keep, assume_unique=False)
        if len(indices) == 0:
            return []
        if len(indices) < table.num_rows:
            table = table.take(indices)
        stored_rows = table.to_pylist()
        decode_view = self._stored_schema.create_schema_view(
            [c for c in table.column_names if c in self._stored_schema.fields]
        )
        try:
            staged = {}
            for name in self._device_fields:
                # whole-row-group batched stage 1 (one native call), same as the batch
                # path; decode_row then just picks up each row's pre-staged payload
                field = decode_view.fields.get(name)
                batch_stage = getattr(field.codec, "host_stage_decode_batch", None) \
                    if field is not None else None
                if batch_stage is not None:
                    try:
                        staged[name] = batch_stage(
                            field, [r.get(name) for r in stored_rows])
                    except DecodeFieldError:
                        raise
                    except Exception as e:  # noqa: BLE001 — decode_row contract
                        raise DecodeFieldError(
                            "Unable to decode field %r: %s" % (name, e)) from e
            rows = []
            for i, r in enumerate(stored_rows):
                prestaged = {name: col[i] for name, col in staged.items()}
                rows.append(decode_row(r, decode_view, self._device_fields, prestaged))
        except DecodeFieldError as e:
            raise _annotate_decode_error(e, piece) from e
        return rows

    def _form_ngram_dicts(self, rows):
        schema = self._ngram_schema if self._ngram_schema is not None else self._read_schema
        windows = self._ngram.form_ngram(rows, schema)
        return [{offset: nt._asdict() for offset, nt in w.items()} for w in windows]


class ArrowWorker(_WorkerBase):
    """Vectorized batch path (reference ``ArrowReaderWorker``): row group → columnar numpy dict.

    Stays columnar the whole way — the shape the JAX loader wants. TransformSpec runs on a
    pandas DataFrame (reference contract). With an ``ngram``, the columnar batch is
    windowed in-worker (post-transform) via :func:`petastorm_tpu.ngram.form_ngram_columns`
    into flat ``offset/field`` columns — a TPU-first extension; the reference's NGram
    exists only on the per-row path (petastorm/ngram.py ~L40).
    """

    #: the batch path delivers columns — the shape the pass-through can ride
    _pagedec_supported = True

    def __init__(self, *args, ngram=None, **kwargs):
        super().__init__(*args, **kwargs)
        self._ngram = ngram
        #: deliver ragged numeric list columns as RaggedColumn values (set through
        #: ``Reader.keep_ragged`` by a packing DataLoader; in-process pools only)
        self.ragged_columnar = False

    def __getstate__(self):
        state = super().__getstate__()
        state["ragged_columnar"] = False  # a pool child sends per-row arrays
        return state

    def __call__(self, item):
        piece, _partition = item
        cache_key = _cache_key(piece, self._read_schema, self._predicate, self._filters,
                               item[1], self._drop_partitions, self._seed,
                               self._device_fields)
        columns = self._cache_get(cache_key, lambda: self._load_columns(item))
        spec = self._transform_spec
        if spec is not None and not spec.device:
            with _prov.span("transform"):
                if getattr(spec, "declarative", False):
                    # compiled declarative pipeline: fused vectorized kernels
                    # over the columnar batch — no pandas round trip, untouched
                    # columns stay the original zero-copy views
                    columns = spec.apply_columns(columns)
                elif spec.func is not None:
                    import pandas as pd

                    pdf = pd.DataFrame(
                        {k: list(v) if v.ndim > 1 else v
                         for k, v in columns.items()})
                    pdf = spec.func(pdf)
                    from petastorm_tpu.utils import stack_as_column

                    columns = {}
                    for name in pdf.columns:
                        series = pdf[name]
                        if series.dtype == object:
                            # tensor rows: one stack; scalar object columns
                            # (strings/decimals) degrade to an object array
                            columns[name] = stack_as_column(series.to_list())
                        else:
                            # no per-row materialization
                            columns[name] = series.to_numpy()
        if self._ngram is not None:
            from petastorm_tpu.ngram import form_ngram_columns

            columns = form_ngram_columns(columns, self._ngram)
        return columns

    def _first_read_columns(self):
        # the batch path reads everything at once: wanted + mask columns
        wanted = list(self._read_schema.fields.keys())
        extra = set()
        if self._predicate:
            extra |= set(self._predicate.get_fields())
        if self._filters:
            extra |= _dnf_fields(self._filters)
        return sorted(set(wanted) | extra)

    def _load_columns(self, item):
        piece, partition = item
        wanted = list(self._read_schema.fields.keys())
        # compressed-page pass-through (ISSUE 14): eligible fixed-width
        # columns ship their raw compressed pages as opaque columnar values;
        # the classic read below fetches only the remainder. Eligibility is
        # footer-only here (cheap) — the page walk inside _pagedec_read may
        # still degrade a column back (per-column fallback).
        passthrough = {}
        eligible = self._pagedec_eligible(piece, wanted)
        if eligible:
            # same transient-retry budget as any other read of this piece;
            # PagedecCorruptError is PERMANENT (fails fast -> quarantinable)
            passthrough = self._retry_io(
                lambda: self._pagedec_read(piece, eligible), piece.path,
                "%s row group %d (pagedec)" % (piece.path, piece.row_group))
        read_columns = self._first_read_columns()
        if passthrough:
            read_columns = [c for c in read_columns if c not in passthrough]
        if not read_columns:
            # every wanted column passed through: nothing left to decode on
            # the host, but the generation contract (ISSUE 11) still holds
            if getattr(piece, "generation", None) is not None:
                self._verify_generation(piece)
            return dict(passthrough)
        table = self._read_columns(piece, read_columns)
        mask = self._row_mask(table)
        indices = np.arange(table.num_rows)
        if mask is not None:
            indices = indices[mask]
        if self._drop_partitions > 1:
            keep = self._drop_partition_indices(item, table.num_rows)
            indices = np.intersect1d(indices, keep)
        if len(indices) < table.num_rows:
            table = table.take(indices)
        # a host transform or an NGram window reads rows: they get per-row arrays
        spec = self._transform_spec
        ragged = self.ragged_columnar and self._ngram is None and (
            spec is None or spec.device)
        out = {}
        for name in wanted:
            if name in table.column_names:
                try:
                    out[name] = _column_to_numpy(table, name, self._read_schema,
                                                 self._device_fields, ragged)
                except DecodeFieldError as e:
                    raise _annotate_decode_error(e, piece) from e
                except Exception as e:  # noqa: BLE001 — decode_row contract below
                    field = self._read_schema.fields.get(name)
                    if field is None or field.codec is None:
                        raise  # plain-column conversion bug, not a decode failure
                    raise _annotate_decode_error(
                        DecodeFieldError("Unable to decode field %r: %s" % (name, e)),
                        piece) from e
        out.update(passthrough)
        return out


def _annotate_decode_error(err, piece):
    """Attach the failing row group's identity to a decode error — at pod scale 'which
    file, which group' is the difference between a fixable corpus bug and a mystery."""
    return DecodeFieldError(
        "%s (while decoding %s row group %d)" % (err, piece.path, piece.row_group))


def _merge_tables(head, tail):
    """Column-wise merge of two same-length row-group reads into one table."""
    import pyarrow as pa

    cols = {name: head.column(name) for name in head.column_names}
    cols.update({name: tail.column(name) for name in tail.column_names})
    return pa.table(cols)


def _column_to_numpy(table, name, schema, device_fields=(), ragged_columnar=False):
    """Arrow column → numpy array; decodes codec columns, stacks list columns.

    List columns take the vectorized path: flatten the Arrow child buffer straight to
    numpy and reshape — ``to_pylist`` would materialize every element as a Python object
    (~100x slower on image-sized rows, the data-plane hot loop). A ragged numeric list
    column without nulls keeps Arrow's child values and offsets: a
    :class:`~petastorm_tpu.ops.packing.RaggedColumn` when ``ragged_columnar``, else
    the object array of per-row arrays (views of one copy of the values). Codec
    columns named in ``device_fields`` run only the host half of the two-stage decode
    and come back as an object array of staging payloads the JAX loader finishes on
    device."""
    import pyarrow as pa

    col = table.column(name)
    field = schema.fields.get(name)
    if field is not None and field.codec is not None:
        from petastorm_tpu.codecs import ScalarCodec

        scalar = _scalar_codec_fast_path(col, field)
        if scalar is not None:
            return scalar
        values = None
        if not isinstance(field.codec, ScalarCodec):
            # blob codecs (ndarray/image): zero-copy memoryviews into Arrow buffers
            values = _binary_column_views(col)
        if values is None:
            values = col.to_pylist()
        if name in device_fields:
            from petastorm_tpu.utils import stack_as_column

            batch_stage = getattr(field.codec, "host_stage_decode_batch", None)
            if batch_stage is not None:
                # one native call stages the whole row group (stacked coefficient
                # buffers; per-row payloads are zero-copy views into them)
                staged = batch_stage(field, values)
            else:
                staged = [field.codec.host_stage_decode(field, v) if v is not None
                          else None for v in values]
            return stack_as_column(staged, force_object=True)
        np_dtype = np.dtype(field.numpy_dtype)
        shape_known = field.shape and all(d is not None for d in field.shape)
        if shape_known and np_dtype.kind in "biufc" \
                and not any(v is None for v in values):
            # static-shape tensor column: decode straight into one preallocated array
            # (skips the list-of-arrays + _stack double materialization)
            out = np.empty((len(values),) + tuple(field.shape), dtype=np_dtype)
            decode = field.codec.decode
            for i, v in enumerate(values):
                out[i] = decode(field, v)
            return out
        decoded = [field.codec.decode(field, v) if v is not None else None for v in values]
        return _stack(decoded, field)
    if field is not None and field.shape:
        arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
        stacked = _list_column_to_numpy(arr, field)
        if stacked is not None:
            return stacked
        ragged = _ragged_list_column(arr, field)
        if ragged is not None:
            return ragged if ragged_columnar else ragged.to_rows()
        return _stack(arr.to_pylist(), field)
    return col.to_numpy(zero_copy_only=False)


def _scalar_codec_fast_path(col, field):
    """Vectorized ScalarCodec decode: plain numeric/bool scalar columns are just an
    Arrow→numpy view + dtype cast — no per-row ``codec.decode`` loop. Returns None when
    the fast path does not apply (nulls, strings/decimals/dates, non-scalar codecs)."""
    from petastorm_tpu.codecs import ScalarCodec

    if type(field.codec) is not ScalarCodec or field.shape:
        return None
    np_dtype = np.dtype(field.numpy_dtype)
    if np_dtype.kind not in "biuf":
        return None
    arr = col.combine_chunks() if hasattr(col, "combine_chunks") else col
    if arr.null_count:
        return None
    out = arr.to_numpy(zero_copy_only=False)
    if out.dtype.kind not in "biuf":
        return None
    return out.astype(np_dtype, copy=False)


def _binary_column_views(col):
    """Binary/string column → list of zero-copy memoryview slices into the Arrow data
    buffer (None entries for nulls). Returns None when the column is not binary-like —
    the caller falls back to ``to_pylist``. Avoids materializing one bytes object per
    row on the decode hot path (VERDICT r1 #4)."""
    import pyarrow as pa

    chunks = col.chunks if isinstance(col, pa.ChunkedArray) else [col]
    out = []
    for chunk in chunks:
        t = chunk.type
        if pa.types.is_binary(t) or pa.types.is_string(t):
            odt = np.int32
        elif pa.types.is_large_binary(t) or pa.types.is_large_string(t):
            odt = np.int64
        else:
            return None
        n = len(chunk)
        if n == 0:
            continue
        bufs = chunk.buffers()
        off = chunk.offset
        offsets = np.frombuffer(bufs[1], dtype=odt, count=off + n + 1)[off:]
        data = memoryview(bufs[2]) if bufs[2] is not None else memoryview(b"")
        if chunk.null_count:
            valid = np.asarray(chunk.is_valid())
            out.extend(
                data[offsets[i]:offsets[i + 1]] if valid[i] else None
                for i in range(n)
            )
        else:
            out.extend(data[offsets[i]:offsets[i + 1]] for i in range(n))
    return out


def _list_column_to_numpy(arr, field):
    """Vectorized (fixed-size or uniform) list-of-numeric column → (n, ...) ndarray.

    Returns None when the fast path does not apply (ragged rows, nulls, non-numeric)."""
    import pyarrow as pa

    shape_known = field.shape and all(d is not None for d in field.shape)
    if arr.null_count:
        return None
    if isinstance(arr.type, pa.FixedSizeListType):
        size = arr.type.list_size
        flat = arr.flatten().to_numpy(zero_copy_only=False)  # offset/slice-safe
        if flat.dtype.kind not in "biufc":  # nested/non-numeric: fall back to to_pylist
            return None
        out = flat.reshape(len(arr), size)
    elif pa.types.is_list(arr.type) or pa.types.is_large_list(arr.type):
        offsets = arr.offsets.to_numpy(zero_copy_only=False)
        lengths = np.diff(offsets)
        if len(lengths) == 0 or not (lengths == lengths[0]).all():
            return None  # ragged: caller falls back to object rows
        flat = arr.flatten().to_numpy(zero_copy_only=False)
        if flat.dtype.kind not in "biufc":
            return None
        out = flat.reshape(len(arr), int(lengths[0]))
    else:
        return None
    if shape_known and int(np.prod(field.shape)) == out.shape[1]:
        out = out.reshape((len(out),) + tuple(field.shape))
    np_dtype = np.dtype(field.numpy_dtype)
    if np_dtype.kind in "biufc" and out.dtype != np_dtype:
        out = out.astype(np_dtype)
    return out


def _ragged_list_column(arr, field):
    """A ragged ``list``/``large_list`` column of numbers with no nulls, as a
    :class:`~petastorm_tpu.ops.packing.RaggedColumn` over Arrow's child values (zero
    copy unless the field's dtype differs) and offsets. None when it is not one."""
    import pyarrow as pa

    if not (pa.types.is_list(arr.type) or pa.types.is_large_list(arr.type)):
        return None
    child = arr.values
    if arr.null_count or child.null_count:
        return None
    values = child.to_numpy(zero_copy_only=False)
    if values.dtype.kind not in "biufc":
        return None
    np_dtype = np.dtype(field.numpy_dtype)
    if np_dtype.kind in "biufc" and values.dtype != np_dtype:
        values = values.astype(np_dtype)
    return RaggedColumn(values, arr.offsets.to_numpy(zero_copy_only=False))


def _stack(values, field):
    """Stack per-row values into one array; ragged/object data degrades to an object array."""
    np_dtype = np.dtype(field.numpy_dtype)
    target = None if np_dtype.kind in "OUSM" else np_dtype
    try:
        return np.asarray(values, dtype=target)
    except (ValueError, TypeError):
        arr = np.empty(len(values), dtype=object)
        arr[:] = values
        return arr


def _dnf_fields(filters):
    fields = set()
    for clause in filters:
        terms = [clause] if isinstance(clause[0], str) else clause
        for name, _op, _val in terms:
            fields.add(name)
    return fields


def _dnf_mask(table, filters):
    """Evaluate pyarrow-style DNF filters [(col, op, val), ...] or [[...], [...]] as a mask."""
    def term_mask(name, op, val):
        col = table.column(name).to_numpy(zero_copy_only=False)
        if op in ("=", "=="):
            return col == val
        if op == "!=":
            return col != val
        if op == "<":
            return col < val
        if op == "<=":
            return col <= val
        if op == ">":
            return col > val
        if op == ">=":
            return col >= val
        if op == "in":
            return np.isin(col, list(val))
        if op in ("not in", "not-in"):
            return ~np.isin(col, list(val))
        raise ValueError("Unsupported filter op %r" % op)

    total = None
    for clause in _dnf_clauses(filters):
        cmask = None
        for name, op, val in clause:
            t = term_mask(name, op, val)
            cmask = t if cmask is None else (cmask & t)
        total = cmask if total is None else (total | cmask)
    return np.asarray(total, dtype=bool)


def _plan_pieces(pieces, filters, predicate, shard_count=None):
    """Plan-time pruning pipeline: hive partition resolution + directory pruning +
    row-group statistics pruning, with predicate-implied clauses conjoined for the
    pruning. Returns ``(pieces, partition_info, filters)`` where ``filters`` is the
    normalized set the workers run as the row-level mask.

    The implied clauses are PLAN-TIME-ONLY: the returned ``filters`` are the user's
    (normalized), so workers don't re-evaluate — or embed in cache keys — value
    lists the predicate itself already enforces as the row mask.

    If the predicate-implied clauses alone prove the plan empty, a minimal piece set
    (one per shard) is retained: a predicate that matches nothing must yield an
    EMPTY read (reference semantics — predicates never fail construction, and the
    retained row groups mask to zero rows), while an over-filtering user
    ``filters`` still raises ``NoDataAvailableError``."""
    out, partition_info, norm_user = _resolve_partitions(pieces, filters)
    out = _prune_by_stats(out, norm_user)
    implied = None
    if predicate is not None:
        from petastorm_tpu.predicates import implied_dnf_filters

        implied = implied_dnf_filters(predicate)
    if implied and out:
        # Sequential pruning passes are equivalent to conjoining the clause sets
        # (satisfiability is checked per term), so the implied clauses prune the
        # already-user-pruned set directly — no DNF cross product needed.
        logger.debug("Predicate-implied pruning clauses: %s", implied)
        kept = out
        if partition_info:
            from petastorm_tpu.partitions import normalize_filters, prune_pieces

            implied = normalize_filters(implied, partition_info)
            kept = prune_pieces(kept, partition_info, implied)
        kept = _prune_by_stats(kept, implied)
        # Never hand a shard zero pieces: round-robin assignment over fewer pieces
        # than shards would fail construction on the starved shards, where the same
        # predicate without pruning yields an empty read there. Pad with unpruned
        # survivors (they mask to zero rows) up to one piece per shard — a bounded
        # waste (one re-read row group per starved shard per epoch) accepted over
        # teaching Reader an empty-plan mode.
        min_pieces = max(1, int(shard_count or 1))
        if len(kept) < min_pieces:
            have = {(p.path, p.row_group) for p in kept}
            extra = [p for p in out if (p.path, p.row_group) not in have]
            kept = kept + extra[:min_pieces - len(kept)]
        out = kept
    return ([p._replace(stats=None) if p.stats else p for p in out],
            partition_info, norm_user)


def _dnf_clauses(filters):
    """Normalize pyarrow-style DNF filters to a list of AND-clauses: accepts both the
    flat ``[(col, op, val), ...]`` form and the ``[[...], [...]]`` OR-of-ANDs form.
    Shared by the row-level mask (``_dnf_mask``) and plan-time statistics pruning
    (``_prune_by_stats``) so their clause semantics cannot drift."""
    return [filters] if isinstance(filters[0][0], str) else filters


def _stable_repr(value):
    """Deterministic repr for cache keys (sets/dicts get sorted)."""
    if isinstance(value, (set, frozenset)):
        return "{%s}" % ",".join(sorted(repr(v) for v in value))
    if isinstance(value, dict):
        return "{%s}" % ",".join(
            "%r:%s" % (k, _stable_repr(v)) for k, v in sorted(value.items())
        )
    if isinstance(value, (list, tuple)):
        return "[%s]" % ",".join(_stable_repr(v) for v in value)
    return repr(value)


_RUN_SALT = None

_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")


def _predicate_key(predicate):
    """Stable identity for a predicate: class + parameters. Callables are keyed by
    bytecode + consts + DEFAULTS + CLOSURE VALUES (ADVICE r1: two lambdas with the same
    bytecode but different captured thresholds must not collide in a persistent cache);
    repr would embed a memory address — unstable across runs and reusable across
    different lambdas."""
    import hashlib

    global _RUN_SALT
    parts = [type(predicate).__name__]
    for name, value in sorted(vars(predicate).items()):
        if callable(value):
            code = getattr(value, "__code__", None)
            payload = None
            if code is not None:
                payload = [code.co_code, repr(code.co_consts).encode("utf-8")]
                defaults = getattr(value, "__defaults__", None)
                if defaults:
                    payload.append(_stable_repr(defaults).encode("utf-8"))
                kwdefaults = getattr(value, "__kwdefaults__", None)
                if kwdefaults:
                    payload.append(_stable_repr(kwdefaults).encode("utf-8"))
                closure = getattr(value, "__closure__", None)
                if closure:
                    try:
                        cells = tuple(c.cell_contents for c in closure)
                        payload.append(_stable_repr(cells).encode("utf-8"))
                    except ValueError:  # unreadable cell: treat as unkeyable
                        payload = None
            if payload is not None and any(_ADDR_RE.search(p.decode("utf-8", "ignore"))
                                           for p in payload[1:]):
                # captured objects whose repr embeds a memory address ('<function f at
                # 0x..>') are unstable across runs AND can collide on address reuse —
                # the exact poisoning class this key exists to prevent; salt instead
                payload = None
            if payload is not None:
                digest = hashlib.sha256(b"\x00".join(payload)).hexdigest()
                parts.append("%s=fn:%s" % (name, digest))
            else:
                # unkeyable callable: salt the key per RUN so in-memory reuse works but
                # a persistent cache from another run can never serve mismatched rows
                # (id() alone can recur across runs — ADVICE r1)
                if _RUN_SALT is None:
                    import os as _os

                    _RUN_SALT = _os.urandom(8).hex()
                parts.append("%s=unkeyable:%s:%d" % (name, _RUN_SALT, id(value)))
        else:
            parts.append("%s=%s" % (name, _stable_repr(value)))
    return "|".join(parts)


def _cache_key(piece, schema, predicate, filters, partition, num_partitions, seed,
               device_fields=frozenset()):
    predicate_key = ""
    if predicate is not None:
        predicate_key = _predicate_key(predicate)
    parts = [
        piece.path,
        str(piece.row_group),
        ",".join(schema.fields.keys()),
        predicate_key,
        repr(filters) if filters else "",
        "%s/%s" % (partition, num_partitions),
        str(seed) if num_partitions > 1 else "",
    ]
    if device_fields:
        # device-staged payloads differ from host-decoded ones — never cross-serve.
        # Appended only when active so pre-existing persistent cache keys stay valid.
        parts.append("dev:%s" % ",".join(sorted(device_fields)))
    generation = getattr(piece, "generation", None)  # duck-typed test pieces
    if generation is not None:
        # generation-scoped caching (ISSUE 11): a rewritten source file — even
        # one colliding on size AND mtime — maps to a NEW key, so no tier can
        # serve the old generation's decoded payload to the new generation's
        # plan items. Appended only when dataset watching stamped a token, so
        # persistent cache keys from watch-less runs stay valid.
        parts.append("gen:%s" % generation)
    return "|".join(parts)


# --------------------------------------------------------------------------------------
# Reader
# --------------------------------------------------------------------------------------


class Reader:
    """Iterates decoded rows (per-row path) or columnar batches (batch path).

    Reference: ``Reader`` petastorm/reader.py ~L330. Context-manager protocol, ``reset()``,
    ``stop()``/``join()``, ``last_row_consumed``; checkpointable via ``state_dict()`` (our
    upgrade — the plan cursor, SURVEY.md §6).
    """

    def __init__(self, filesystem, path, schema, stored_schema, worker, pieces,
                 num_epochs=1, shuffle_row_groups=True, seed=None,
                 cur_shard=None, shard_count=None, shard_seed=None,
                 shuffle_row_drop_partitions=1,
                 reader_pool_type="thread", workers_count=4, results_queue_size=16,
                 is_batched_reader=False, ngram=None, results_timeout_s=300.0,
                 wire_serializer="pickle", worker_respawns=None, io_options=None,
                 recovery=None, provenance=None, watch=None, watch_paths=None,
                 transport=None, tenant=None):
        from petastorm_tpu.obs import tenant as _tenant_mod

        #: resolved TenantContext (ISSUE 18) or None: explicit arg wins, else
        #: the ambient context / PTPU_TENANT env; invalid explicit slugs raise
        self.tenant_context = _tenant_mod.resolve(tenant)
        self._fs = filesystem
        self._path = path
        self.schema = schema
        self._stored_schema = stored_schema
        self._worker = worker
        self.is_batched_reader = is_batched_reader
        self.ngram = ngram
        self._ngram_views = {}
        self._row_type = schema.make_namedtuple_type()

        self.cur_shard = cur_shard
        self.shard_count = shard_count
        shard_idx = shard_indices(len(pieces), cur_shard, shard_count, shard_seed) \
            if shard_count else np.arange(len(pieces))
        sharded = [pieces[int(i)] for i in shard_idx]
        if not sharded and pieces:
            logger.warning("Shard %s/%s received no row groups", cur_shard, shard_count)
        items = [
            (piece, partition)
            for piece in sharded
            for partition in range(max(1, shuffle_row_drop_partitions))
        ]
        if not items:
            raise NoDataAvailableError(
                "No row groups to read (empty dataset, over-filtering selector/predicate, or "
                "an empty shard)"
            )
        #: the shuffle seed; a loader's crop boxes draw under it unless given one
        self.seed = seed if seed is not None else shard_seed
        self._plan = EpochPlan(items, num_epochs=num_epochs, shuffle=shuffle_row_groups,
                               seed=self.seed,
                               with_epoch=True)
        self._num_items = len(items)
        self._io_options = IoOptions.normalize(io_options)
        self._recovery = RecoveryOptions.resolve(recovery,
                                                 worker_respawns=worker_respawns)
        #: every plan item skipped as poison under on_poison='quarantine'
        #: (ISSUE 7) — empty (falsy) on a healthy run
        self.quarantine_report = QuarantineReport()
        #: quarantined items whose footer was never readable (row loss is
        #: unquantifiable — ISSUE 8 satellite); surfaced in :meth:`io_stats`
        self._footer_unreadable = 0
        self._pool_args = (reader_pool_type, workers_count, results_queue_size,
                           results_timeout_s, wire_serializer,
                           self._recovery.worker_respawns, self._io_options,
                           self._recovery, transport)
        self._executor = None
        self._results_iter = None
        self._buffer = []
        self._buffer_pos = 0
        self._buffer_tag = None  # (epoch, ordinal) of the row-group feeding _buffer
        self._consumed = {}  # epoch -> set(ordinal): fully-delivered work items
        self._resume_epoch = 0  # every epoch below this is fully consumed
        self.last_row_consumed = False
        self.stopped = False
        #: compressed-page pass-through adoption (ISSUE 14): False (the
        #: default) materializes PassthroughColumn values into decoded
        #: arrays at delivery — loader-less consumers see ordinary batches;
        #: the DataLoader sets True and finishes the inflate itself (on
        #: device when a non-CPU backend is live)
        self.keep_passthrough = False
        self._keep_ragged = False
        #: lease of the CURRENT batch/row-buffer on a view-mode wire — held
        #: until the consumer asks for the next batch (or calls release_batch()
        #: / takes ownership via take_lease())
        self._held_lease = None
        #: every lease this reader ever delivered that is possibly still
        #: retained by a consumer — revoked wholesale when reset() rebuilds the
        #: executor, so stale views raise LeaseRevoked instead of reading a
        #: recycled slab (weak: released leases fall out on their own)
        self._issued_leases = weakref.WeakSet()
        #: optional obs.provenance.ProvenanceRecorder (ISSUE 10): per-item
        #: causal records — deliveries noted here feed batch attribution.
        #: Set BEFORE _start(): the executor begins claiming plan items the
        #: moment it starts, and a recorder attached later (the DataLoader's
        #: set_provenance) misses every item a small plan already drained.
        self._prov = provenance
        #: dataset-watch plane (ISSUE 11): a watcher thread that diffs the
        #: piece set every interval and feeds _apply_plan_delta; None when
        #: the dataset is declared frozen (the default)
        self._drop_partitions = max(1, shuffle_row_drop_partitions)
        self._watcher = None
        if watch is not None:
            from petastorm_tpu.dataset.watch import DatasetWatcher

            self._watcher = DatasetWatcher(filesystem, path, watch,
                                           on_delta=self._apply_plan_delta)
            # known_paths: every file that existed at plan time, including
            # plan-time-pruned ones — the first tick must not re-add what the
            # user's filters/selector excluded
            self._watcher.prime(pieces, known_paths=watch_paths)
        self._start()

    def _start(self):
        (pool_type, workers_count, queue_size, timeout_s, serializer,
         respawns, io_options, recovery, transport) = self._pool_args
        reopen = getattr(self._worker, "reopen", None)
        if reopen is not None:  # reset()/restore after join() closed the IO runtime
            reopen()
        self._executor = make_executor(
            pool_type, workers_count, queue_size, timeout_s, serializer,
            respawns, io_options=io_options, recovery=recovery,
            transport=transport)
        monitor = getattr(self, "_health_monitor", None)
        if monitor is not None:
            # reset()/restore rebuilds the executor — re-attach BEFORE start so
            # a process pool hands its children the monitor-era handshake
            fn = getattr(self._executor, "set_health", None)
            if fn is not None:
                fn(monitor)
        if self._prov is not None:
            # provenance survives reset()'s executor rebuild like health does
            # (join() disarmed an auto-owned recorder; re-arm is idempotent
            # and fails loud if a DIFFERENT recorder took the slot meanwhile)
            self._prov.arm()
            fn = getattr(self._executor, "set_provenance", None)
            if fn is not None:
                fn(self._prov)
        self._executor.start(_Tagged(self._worker, tenant=self.tenant_context),
                             self._plan)
        self._results_iter = self._executor.results()
        self.stopped = False
        watcher = getattr(self, "_watcher", None)
        if watcher is not None:
            # (re)armed LAST: a failed executor start must not leak a watch
            # thread, and reset()/restore restart watching with the stream
            watcher.start()

    def _mark_consumed(self, tag):
        if tag is None:
            return
        epoch, ordinal = tag
        self._consumed.setdefault(epoch, set()).add(ordinal)
        # advance the watermark: epochs below _resume_epoch are fully consumed
        # (bounded state). The per-epoch denominator comes from the PLAN, not
        # a fixed num_items — a mid-run extension (ISSUE 11) grows later
        # epochs without wedging the watermark on earlier ones.
        while len(self._consumed.get(self._resume_epoch, ())) \
                >= self._plan.items_in_epoch(self._resume_epoch):
            del self._consumed[self._resume_epoch]
            self._resume_epoch += 1

    # -- dataset-watch plane (ISSUE 11) --------------------------------------------------

    @property
    def dataset_watcher(self):
        """The live :class:`~petastorm_tpu.dataset.watch.DatasetWatcher`, or
        ``None`` when the reader was opened without ``watch=``."""
        return self._watcher

    def _apply_plan_delta(self, delta):
        """The watcher's delta seam (runs on the watch thread).

        Added files extend the CURRENT epoch (fresh paths cannot mix
        generations); a rewritten file's new generation is deferred to the
        NEXT epoch (the old generation may already have delivered rows this
        epoch); removed/rewritten old-generation pieces get their cache
        entries dropped — their still-pending plan items fail their
        generation check at read time and quarantine as
        ``piece_removed``/``piece_rewritten``, charged to the watermark like
        any other skip."""
        stale = [p for _path, pieces in delta.removed for p in pieces]
        stale += [p for _path, old, _new in delta.rewritten for p in old]
        if stale:
            invalidate = getattr(self._worker, "invalidate_pieces", None)
            if invalidate is not None:
                invalidate(stale)
        extended = False
        added = [p for p in delta.added if self._owns_piece(p)]
        if added:
            self._plan.extend(self._to_items(added), defer=False)
            extended = True
        replanned = [p for _path, _old, new in delta.rewritten for p in new
                     if self._owns_piece(p)]
        if replanned:
            self._plan.extend(self._to_items(replanned), defer=True)
            extended = True
        if extended:
            self._num_items = len(self._plan.items)
            from petastorm_tpu.dataset.watch import watch_metrics

            watch_metrics()["plan_extensions"].inc()

    def _items_identity_crc(self, count):
        """crc32 over the identity (path:row_group:partition) of the first
        ``count`` plan items in ordinal order — what binds a checkpoint's
        consumed-ordinal map to the item order it was taken over."""
        import zlib

        h = 0
        for piece, partition in self._plan.items[:count]:
            h = zlib.crc32(("%s:%s:%s" % (piece.path, piece.row_group,
                                          partition)).encode("utf-8"), h)
        return h & 0xFFFFFFFF

    def _owns_piece(self, piece):
        """Deterministic shard assignment for watch-discovered pieces: every
        host computes the same crc32 hash, so the shards' extensions stay
        disjoint and their union exact — the same zero-communication property
        the initial round-robin sharding has."""
        if not self.shard_count:
            return True
        import zlib

        key = "%s:%s" % (piece.path, piece.row_group)
        return zlib.crc32(key.encode("utf-8")) % self.shard_count \
            == self.cur_shard

    def _to_items(self, pieces):
        return [(piece, partition) for piece in pieces
                for partition in range(self._drop_partitions)]

    def _absorb_quarantine(self, marker):
        """Absorb a :class:`~petastorm_tpu.recovery.QuarantinedItem` marker
        (ISSUE 7): the poisoned plan item is recorded in the quarantine report,
        counted (``ptpu_quarantined_{items,rows}_total``), and — crucially —
        **charged against the consumed-ordinal watermark** exactly like a
        delivered item, so a checkpoint taken after the skip resumes without
        replaying it (and without losing anything else). The consumer never
        sees the marker."""
        epoch, ordinal, inner = marker.item
        piece = inner[0] if isinstance(inner, tuple) and inner else inner
        path = getattr(piece, "path", repr(inner))
        row_group = getattr(piece, "row_group", -1)
        num_rows = getattr(piece, "num_rows", None)
        if num_rows is None or num_rows < 0:
            # planning's KV fast path leaves num_rows=-1 by design (it never
            # opens footers) — resolve the real count from the footer now so
            # the quarantine ledger says how many rows were lost; only when
            # that READ fails is the footer genuinely unreadable (ISSUE 8
            # satellite: this used to collapse to -1 silently either way)
            num_rows = self._resolve_quarantined_rows(path, row_group)
        # dataset-mutation classification (ISSUE 11): a skip caused by the
        # file vanishing or changing generation mid-run gets its own kind and
        # degradation cause — an operator must tell "bad data" apart from
        # "the dataset moved under me" without reading exception chains
        kind = marker.kind
        cause = "quarantined"
        if isinstance(marker.error, PieceRewrittenError):
            kind = cause = "piece_rewritten"
            from petastorm_tpu.dataset.watch import watch_metrics

            # counted HERE (the consumer process), not at the worker's
            # detection site — a pool child's registry never reaches the
            # parent's export/panel
            watch_metrics()["generation_conflicts"].inc()
        elif isinstance(marker.error, PieceRemovedError):
            kind = cause = "piece_removed"
        entry = QuarantineEntry(epoch, ordinal, path, row_group, num_rows,
                                marker.error, marker.attempts, kind)
        self.quarantine_report.add(entry)
        count_quarantined(num_rows)
        if self.tenant_context is not None:
            from petastorm_tpu.obs import tenant as _tenant_mod

            _tenant_mod.charge("quarantined", max(0, num_rows or 0),
                               label=self.tenant_context.tenant)
        from petastorm_tpu.obs.log import degradation

        degradation(
            cause,
            "poison item quarantined after %d attempt(s): %s row group %s "
            "(epoch=%s ordinal=%s, %s) — skipped, charged to the checkpoint "
            "watermark; see Reader.quarantine_report", marker.attempts, path,
            row_group, epoch, ordinal, kind, once=False)
        if self._prov is not None:
            # exactly-once beside delivery: a quarantined item never enters
            # the delivery FIFO, so the ledgers stay disjoint
            self._prov.note_quarantined(epoch, ordinal, marker.attempts,
                                        marker.kind)
        self._mark_consumed((epoch, ordinal))

    def _resolve_quarantined_rows(self, path, row_group):
        """The quarantined row group's row count from its footer (via the
        shared cache — usually already parsed), or -1 with a
        ``footer_unreadable`` degradation when the footer cannot be read or
        does not contain the group (quarantine is rare; one bounded footer
        read per skipped item is worth an exact loss ledger)."""
        try:
            from petastorm_tpu.io.footercache import shared_footer_cache

            entry = shared_footer_cache().get(self._fs, path)
            return entry.row_group_rows[row_group]
        except Exception as e:  # noqa: BLE001 — unreadable/mismatched footer
            self._footer_unreadable += 1
            from petastorm_tpu.obs.log import degradation

            degradation(
                "footer_unreadable",
                "quarantined %s row group %s has no readable footer (%s): the "
                "skipped row count is UNKNOWN (recorded as -1 in the "
                "quarantine report; see Reader.io_stats()['footer_unreadable'])",
                path, row_group, e, once=False)
            return -1

    # -- iteration ----------------------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self.is_batched_reader:
            return self._next_batch()
        return self._next_row()

    def _next_row(self):
        while True:
            if self._buffer_pos < len(self._buffer):
                row = self._buffer[self._buffer_pos]
                self._buffer_pos += 1
                if self._buffer_pos >= len(self._buffer):
                    # last row of this row group delivered -> safe to mark consumed
                    self._mark_consumed(self._buffer_tag)
                    self._buffer_tag = None
                return self._wrap_row(row)
            # moving past the drained buffer: its slab (shm view wire) returns to
            # the ring — rows handed out so far must already be done with
            self.release_batch()
            nxt = next(self._results_iter, None)
            if nxt is None:
                if not getattr(self._executor, "truncated", False):
                    self.last_row_consumed = True
                raise StopIteration
            if isinstance(nxt, QuarantinedItem):
                self._absorb_quarantine(nxt)
                continue
            epoch, ordinal, payload = nxt
            self._held_lease = self._register_lease(
                getattr(payload, "lease", None))
            if not payload:
                self._mark_consumed((epoch, ordinal))  # fully-filtered group
                continue
            if self._prov is not None:
                self._prov.note_delivery(epoch, ordinal, len(payload))
            if self.tenant_context is not None:
                # charged at DELIVERY (the consumer-visible boundary), so
                # per-tenant rows == what the tenant actually received
                from petastorm_tpu.obs import tenant as _tenant_mod

                _tenant_mod.charge("rows", len(payload),
                                   label=self.tenant_context.tenant)
            self._buffer = payload
            self._buffer_pos = 0
            self._buffer_tag = (epoch, ordinal)

    def _wrap_row(self, row):
        if self.ngram is not None:
            out = {}
            for offset, values in row.items():
                view = self._ngram_views.get(offset)
                if view is None:
                    view = self._ngram_views[offset] = self.schema.create_schema_view(
                        self.ngram.get_field_names_at_timestep(offset)
                    )
                out[offset] = view.make_namedtuple(**values)
            return out
        return self._row_type(**{name: row.get(name) for name in self.schema.fields})

    @property
    def keep_ragged(self):
        """Whether ragged numeric list columns are delivered columnar, as
        :class:`~petastorm_tpu.ops.packing.RaggedColumn` values (one ``values`` and
        one ``offsets`` array a delivery). False (the default) delivers object
        arrays of per-row arrays. A packing ``DataLoader`` sets it True; in-process
        pools then skip the per-row arrays, while a process pool's children still
        send them over the wire (the packer takes both)."""
        return self._keep_ragged

    @keep_ragged.setter
    def keep_ragged(self, on):
        self._keep_ragged = bool(on)
        if hasattr(self._worker, "ragged_columnar"):
            self._worker.ragged_columnar = self._keep_ragged

    def _next_batch(self):
        # previous batch's slab (shm view wire) returns to the ring: a batch's
        # views stay valid until the consumer asks for the NEXT batch
        self.release_batch()
        while True:
            nxt = next(self._results_iter, None)
            if nxt is None:
                if not getattr(self._executor, "truncated", False):
                    self.last_row_consumed = True
                raise StopIteration
            if isinstance(nxt, QuarantinedItem):
                self._absorb_quarantine(nxt)
                continue
            epoch, ordinal, columns = nxt
            if isinstance(columns, dict):
                self._held_lease = self._register_lease(
                    columns.pop(_SHM_LEASE_KEY, None))
            self._mark_consumed((epoch, ordinal))  # batch delivery is atomic
            if not columns or len(next(iter(columns.values()))) == 0:
                self.release_batch()
                continue  # fully-filtered (or windowless) row group: skip
            if self._prov is not None:
                self._prov.note_delivery(
                    epoch, ordinal, len(next(iter(columns.values()))))
            if self.tenant_context is not None:
                from petastorm_tpu.obs import tenant as _tenant_mod

                _tenant_mod.charge("rows", len(next(iter(columns.values()))),
                                   label=self.tenant_context.tenant)
            if not self.keep_passthrough:
                # no loader adopted the pass-through: this consumer expects
                # decoded arrays — the numpy reference twin IS the designed
                # host decode for loader-less readers (no degradation)
                from petastorm_tpu.io.pagedec import materialize_columns

                columns = materialize_columns(columns)
            if not self._keep_ragged:
                # a delivery made while a packing loader held the reader
                columns = {k: v.to_rows() if isinstance(v, RaggedColumn) else v
                           for k, v in columns.items()}
            if self.ngram is not None:
                # flat 'offset/field' window columns cannot be namedtuple
                # attributes — batched NGram delivers plain dicts
                return dict(columns)
            return self._row_type(**{name: columns.get(name)
                                     for name in self.schema.fields})

    # -- lease-backed wire integration ---------------------------------------------------

    def _register_lease(self, lease):
        """Track a delivered lease for revocation: ``reset()`` rebuilds the
        executor (and with it the slab ring backing any outstanding views), so
        every lease issued by the PREVIOUS executor generation must be revoked
        there — a consumer holding one across the rebuild gets a clear
        :class:`~petastorm_tpu.errors.LeaseRevoked`, never recycled memory."""
        if lease is not None and hasattr(lease, "revoke"):
            self._issued_leases.add(lease)
        return lease

    def take_lease(self):
        """Transfer ownership of the CURRENT batch's lease to the caller (the
        zero-copy DataLoader path): the reader will no longer release it at the
        next fetch — the caller must ``release()`` it when the batch's buffers
        are done (or rely on refcount GC, counted as a leak). Returns ``None``
        when the current delivery is not lease-backed (thread/dummy pools,
        socket wires, per-item slab fallbacks)."""
        lease, self._held_lease = self._held_lease, None
        return lease

    def release_batch(self):
        """Return the current batch's shared-memory slab to the pool's ring (shm
        VIEW wire only; a no-op on every other pool/wire configuration).

        On ``wire_serializer='shm-view'``/``'shm-arrow-view'`` the arrays of the
        most recent batch are zero-copy read-only views into a pool-owned slab.
        They stay valid until the next ``__next__()`` call releases them
        implicitly; consumers that finish a batch early (e.g. right after a
        ``jax.device_put``) call this to return the slab sooner. After the call
        the previous batch's arrays must not be touched."""
        lease, self._held_lease = self._held_lease, None
        if lease is not None:
            lease.release()

    def wire_stats(self):
        """Process-pool wire gauges (shm slab occupancy, bytes through shared
        memory, socket fallbacks, acquire wait) — {} for thread/dummy pools and
        socket wires. Exported through ``PipelineStats`` by the DataLoader."""
        fn = getattr(self._executor, "wire_stats", None)
        return fn() if fn is not None else {}

    def io_stats(self):
        """Async-read-path gauges (readahead hit/miss/pending/bytes, memcache,
        dispatch steals) — live for thread/dummy pools, where the worker shares
        this process; a process pool reports only the parent-side dispatch
        stats (children keep their IO counters in their own registries).
        Exported as ``ptpu_io_*`` families by the DataLoader's collector."""
        out = {}
        fn = getattr(self._worker, "io_stats", None)
        if fn is not None:
            out.update(fn() or {})
        fn = getattr(self._executor, "dispatch_stats", None)
        if fn is not None:
            out.update(fn() or {})
        if self._footer_unreadable:
            out["footer_unreadable"] = self._footer_unreadable
        if self._watcher is not None:
            out.update(self._watcher.stats())
        return out

    def register_metrics(self, registry):
        """Export this reader's wire AND io gauges onto a
        :class:`petastorm_tpu.obs.MetricsRegistry` as live ``ptpu_wire_*`` /
        ``ptpu_io_*`` families (pull-mode — the executor hot path is
        untouched). For readers consumed WITHOUT a ``DataLoader`` (which
        wires this itself via ``metrics=``) — paired with a
        :class:`petastorm_tpu.obs.serve.MetricsServer` over the registry this
        is the scrape seam for loader-less pipelines. Returns the collector
        handles for ``registry.unregister_collector``."""
        return [registry.register_collector("wire", self.wire_stats),
                registry.register_collector("io", self.io_stats)]

    def set_trace(self, tracer):
        """Attach a :class:`petastorm_tpu.trace.TraceRecorder` to the pool wire
        (records ``shm.acquire_wait`` spans) and the worker's readahead pool
        (``io.readahead``/``io.wait`` spans); the DataLoader wires its own."""
        fn = getattr(self._executor, "set_trace", None)
        if fn is not None:
            fn(tracer)
        fn = getattr(self._worker, "set_trace", None)
        if fn is not None:
            fn(tracer)

    def set_provenance(self, recorder):
        """Attach a :class:`petastorm_tpu.obs.provenance.ProvenanceRecorder`
        (ISSUE 10): per-item deliveries/quarantines are noted here and the
        executor records wire spans + merges pool-child item spans onto it.
        Survives ``reset()``'s executor rebuild. The DataLoader wires this
        from ``provenance=``; call it directly for loader-less readers (pair
        with ``recorder.arm()`` so worker-thread spans are captured)."""
        self._prov = recorder
        fn = getattr(self._executor, "set_provenance", None)
        if fn is not None:
            fn(recorder)

    def set_health(self, monitor):
        """Attach a :class:`petastorm_tpu.obs.health.HealthMonitor` (ISSUE 5):
        executor workers / pool drivers heartbeat per work item (pool children
        additionally gain the SIGUSR1 stack-dump hook), and the worker's
        readahead IO threads heartbeat per background read. The DataLoader
        wires this from ``health=``; call it directly for loader-less
        readers."""
        self._health_monitor = monitor  # survives reset()'s executor rebuild
        fn = getattr(self._executor, "set_health", None)
        if fn is not None:
            fn(monitor)
        fn = getattr(self._worker, "set_health", None)
        if fn is not None:
            fn(monitor)

    # -- live knobs (ISSUE 13) -----------------------------------------------------------

    def resize_workers(self, workers_count):
        """Grow/shrink this reader's worker fleet LIVE (thread and process
        pools; ``None`` on the sync pool, which has no fleet). Grow spawns;
        shrink drains between items — never kills mid-item — and returns the
        retiring workers' claims to the dispatcher, so the delivered row set
        and the checkpoint watermark are identical to an un-resized run.
        ``reset()`` rebuilds the executor at the CONFIGURED count (a retune
        is runtime state, not config)."""
        fn = getattr(self._executor, "resize", None)
        if fn is None:
            return None
        return fn(workers_count)

    def live_workers(self):
        """Workers currently running (including ones draining toward a
        shrink target), or ``None`` for pools without a fleet."""
        return getattr(self._executor, "alive_workers", None)

    def apply_readahead_depth(self, depth):
        """Retune the readahead window live: the worker's pool depth (and IO
        threads), AND the dispatcher's per-worker claim lookahead — the claim
        is the prefetch hint window, so depth without lookahead would starve
        the deeper pool of hints."""
        fn = getattr(self._worker, "apply_readahead_depth", None)
        applied = fn(depth) if fn is not None else max(1, int(depth))
        set_lookahead = getattr(self._executor, "set_lookahead", None)
        if set_lookahead is not None and self._io_options.readahead:
            set_lookahead(applied)
        self._broadcast_child_knobs({"readahead_depth": applied})
        return applied

    def apply_readahead_bytes(self, nbytes):
        fn = getattr(self._worker, "apply_readahead_bytes", None)
        applied = fn(nbytes) if fn is not None else max(0, int(nbytes))
        self._broadcast_child_knobs({"readahead_bytes": applied})
        return applied

    def apply_remote_max_inflight(self, max_inflight):
        fn = getattr(self._worker, "apply_remote_max_inflight", None)
        applied = fn(max_inflight) if fn is not None \
            else max(1, int(max_inflight))
        self._broadcast_child_knobs({"remote_max_inflight": applied})
        return applied

    def apply_hedge_quantile(self, quantile):
        fn = getattr(self._worker, "apply_hedge_quantile", None)
        applied = fn(quantile) if fn is not None \
            else min(0.999, max(0.5, float(quantile)))
        self._broadcast_child_knobs({"hedge_quantile": applied})
        return applied

    def apply_pagedec(self, mode):
        """Retune the compressed-page pass-through mode live (ISSUE 14):
        the controller's revert-to-host-inflate lever. Lands on the worker's
        next read; pool children receive it through the control frame."""
        fn = getattr(self._worker, "apply_pagedec", None)
        applied = fn(mode) if fn is not None else str(mode)
        self._broadcast_child_knobs({"pagedec": applied})
        return applied

    def _broadcast_child_knobs(self, knobs):
        """Live cross-process actuation (ISSUE 14 satellite, PR 13's declared
        leftover): a process pool's children own their IO runtimes — the
        parent-side setters above cannot reach them, so the applied values
        also ride a small control frame on the existing pool wire (beside
        the slab-grant protocol) to every ALREADY-RUNNING child. Thread/dummy
        pools share the worker object and need no frame."""
        fn = getattr(self._executor, "broadcast_io_knobs", None)
        if fn is not None:
            fn(dict(knobs))

    @property
    def wire_views(self):
        """True when batches are zero-copy READ-ONLY slab views (shm view wire):
        buffering consumers must detach (copy) columns before the next fetch."""
        return bool(getattr(self._executor, "wire_views", False))

    # -- lifecycle ----------------------------------------------------------------------

    def reset(self):
        """Restart epochs on an existing reader (reference ``Reader.reset`` ~L700).

        Revokes every outstanding lease this reader issued: the executor
        rebuild below recycles the slab ring those leases' views point into, so
        a batch retained across the reset must fail loud
        (:class:`~petastorm_tpu.errors.LeaseRevoked`) rather than read reused
        memory."""
        self.stop()
        self.join()
        for lease in list(self._issued_leases):
            lease.revoke()
        self._plan.reset()
        self._buffer = []
        self._buffer_pos = 0
        self._buffer_tag = None
        self._consumed = {}
        self._resume_epoch = 0
        self.last_row_consumed = False
        self._start()

    def stop(self):
        self.release_batch()  # a held slab must not survive the stream it came from
        if self._executor is not None:
            self._executor.stop()
        self.stopped = True

    def join(self):
        # the watch thread goes first: a delta applied while the executor is
        # tearing down would extend a plan nobody will drain (reset()/restore
        # re-arm it from _start)
        if self._watcher is not None:
            self._watcher.stop()
        # close the worker's IO runtime FIRST: a stop() mid-stream can leave
        # executor threads blocked inside ReadaheadPool.get, and shutdown()
        # releases those waiters (into the degradation-logged sync fallback)
        # so the executor join below doesn't sit out its full timeout. A
        # reset() lazily rebuilds the pool on the next prefetch.
        close = getattr(self._worker, "close", None)
        if close is not None:
            close()
        if self._executor is not None:
            self._executor.join()
        if self._prov is not None and getattr(self._prov, "_auto_disarm",
                                              False):
            # a recorder the factory built for THIS reader releases the
            # process-global slot here (records stay readable; reset()'s
            # _start re-arms) — without this, a stopped reader would pin
            # ACTIVE forever and the next provenance-enabled reader would
            # refuse to arm. Caller-supplied recorders stay armed.
            self._prov.disarm()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.stop()
        self.join()

    # -- checkpoint ---------------------------------------------------------------------

    def state_dict(self):
        """Exact-resume checkpoint: the consumed-work map, not the dispatch cursor.

        Work items prefetched by the pool but not yet delivered are NOT counted, so resume
        replays them — at-least-once delivery at row-group granularity (a partially-consumed
        row group is replayed in full).
        """
        plan_state = self._plan.state_dict()
        state = {
            "plan": {k: plan_state[k] for k in ("seed", "shuffle", "num_epochs", "num_items")},
            "resume_epoch": self._resume_epoch,
            "consumed": {int(e): sorted(v) for e, v in self._consumed.items()},
            # ordinal-identity binding (ISSUE 11): consumed ordinals are only
            # meaningful against THIS item order. A restore into a reader
            # whose first num_items items differ (a file appended between save
            # and restore that sorts BETWEEN existing names shifts every later
            # ordinal) must fail loudly, not silently replay/lose rows.
            "items_crc": self._items_identity_crc(self._num_items),
        }
        if self.shard_count:
            # shard identity travels with the cursor so a pod restore can route each
            # process its own state (petastorm_tpu.checkpoint global payloads) and a
            # mis-wired restore fails loudly instead of replaying the wrong shard
            state["cur_shard"] = self.cur_shard
            state["shard_count"] = self.shard_count
        return state

    def load_state_dict(self, state):
        if "plan" not in state or "consumed" not in state:
            raise ValueError(
                "not a Reader state (keys: %s) — a WeightedSamplingReader/"
                "InMemDataLoader checkpoint must be restored into the matching "
                "object" % sorted(state))
        self.stop()
        self.join()
        if state["plan"]["num_items"] > self._num_items:
            # fewer checkpointed items than planned is legal under mutable
            # datasets (ISSUE 11: files appended after the save are simply
            # unconsumed); MORE means consumed ordinals would dangle
            raise ValueError(
                "Checkpoint was taken over %d work items; reader has %d"
                % (state["plan"]["num_items"], self._num_items)
            )
        ck_shard = state.get("cur_shard")
        if ck_shard is not None and self.shard_count and ck_shard != self.cur_shard:
            raise ValueError(
                "Checkpoint belongs to shard %s/%s but this reader is shard %s/%s — "
                "resuming would replay the wrong rows"
                % (ck_shard, state.get("shard_count"), self.cur_shard, self.shard_count))
        ck_crc = state.get("items_crc")
        if ck_crc is not None and \
                ck_crc != self._items_identity_crc(state["plan"]["num_items"]):
            raise ValueError(
                "Checkpoint's consumed ordinals do not match this reader's "
                "item order (the first %d work items differ — a file added "
                "between save and restore sorts BETWEEN existing names?): "
                "resuming would replay or lose rows. Mutable datasets must "
                "append files that sort after existing ones (e.g. "
                "monotonically-named parts) for cross-restart resume."
                % state["plan"]["num_items"])
        self._resume_epoch = int(state["resume_epoch"])
        self._consumed = {int(e): set(v) for e, v in state["consumed"].items()}
        self._plan.load_state_dict(
            {**state["plan"], "epoch": self._resume_epoch, "pos": 0}
        )
        self._plan.set_skip(self._consumed)
        self._buffer = []
        self._buffer_pos = 0
        self._buffer_tag = None
        self.last_row_consumed = False
        self._start()


# --------------------------------------------------------------------------------------
# Factories
# --------------------------------------------------------------------------------------


def _host_arena_early(io_opts):
    """Create (or join) the host-wide cache arena BEFORE dataset discovery:
    the factory's own footer reads (schema inference, row-group planning) go
    through the shared :class:`FooterCache`, and publishing those parses
    host-wide only happens when :func:`petastorm_tpu.io.arena.process_arena`
    already exists — an arena born later (in ``_build_read_funnel``) would
    miss the metadata plane, and every attaching process would re-read the
    footers it came here to share."""
    if getattr(io_opts, "arena_bytes", 0):
        from petastorm_tpu.io import arena as arena_mod

        arena_mod.host_arena(io_opts.arena_bytes)


def _build_read_funnel(cache, io_opts, num_epochs=None, tenant=None):
    """The tiered read funnel (ISSUE 8): ``MemCache → LocalDiskCache →
    remote`` as ONE :class:`petastorm_tpu.io.tiers.TieredCache` with per-tier
    hit/byte accounting and the ``disk_admit`` admission policy — replacing
    the old ad-hoc ``MemCache(inner=...)`` stacking. The mem tier exists when
    ``io_options.memcache_bytes`` (or PTPU_MEMCACHE_BYTES) asks for one;
    ``num_epochs == 1`` is the scan hint the ``scan-resistant`` policy
    consumes.

    ``io_options.arena_bytes`` (ISSUE 17) additionally creates — or joins —
    this process's host-wide shared cache arena and threads its picklable
    spec into the mem tier, so every pool child (and any co-resident reader)
    maps ONE warm set of decoded columns instead of refilling its own. The
    arena alone implies a mem tier (local-store budget defaults to the arena
    budget); creation failure degrades warn-once inside ``host_arena``."""
    from petastorm_tpu.io.tiers import TieredCache

    arena_obj = None
    if getattr(io_opts, "arena_bytes", 0):
        from petastorm_tpu.io import arena as arena_mod

        arena_obj = arena_mod.host_arena(io_opts.arena_bytes)
    mem = None
    mem_budget = io_opts.memcache_bytes or (
        io_opts.arena_bytes if arena_obj is not None else 0)
    if mem_budget:
        from petastorm_tpu.io.memcache import MemCache

        mem = MemCache(mem_budget,
                       writable_hits=getattr(io_opts, "memcache_writable_hits",
                                             False),
                       arena=arena_obj)
    return TieredCache(mem=mem, disk=cache,
                       disk_admit=io_opts.remote.disk_admit,
                       single_epoch=num_epochs == 1, tenant=tenant)


def _maybe_compile_pipeline(spec, schema, fs, pieces, cache):
    """Plan a declarative :class:`~petastorm_tpu.ops.tabular.FeaturePipeline`
    against the read schema (ISSUE 9): resolve statistics-dependent op
    parameters — parquet row-group statistics when the footers cover them
    (no data pre-pass), one cached streaming pass otherwise — then compile
    to the fused host kernels (or the jittable device function for
    ``device=True``). Opaque ``TransformSpec``\\ s pass through untouched;
    an already-compiled pipeline (reused across readers) is kept as-is."""
    if spec is None or not getattr(spec, "declarative", False) \
            or getattr(spec, "compiled", False):
        return spec
    reqs = spec.required_statistics(schema)
    stats, sources = {}, {}
    if reqs:
        from petastorm_tpu.io.statscache import resolve_statistics

        stats, sources = resolve_statistics(reqs, fs, pieces, cache=cache)
    spec.compile(schema, statistics=stats)
    spec.stats_info = dict(sources)
    return spec


def _resolve_ngram_schema(schema_fields, stored_schema, predicate):
    """Shared NGram policy for both reader factories: which options NGram forbids
    and how its read-schema view is built. Returns ``(ngram-or-None, read_schema)``."""
    if isinstance(schema_fields, NGram):
        if predicate is not None:
            raise ValueError("NGram readers do not support predicates")
        schema_fields.resolve_regex_field_names(stored_schema)
        return schema_fields, schema_fields.make_schema_view(stored_schema)
    if schema_fields:
        return None, stored_schema.create_schema_view(schema_fields)
    return None, stored_schema


def _resolve_device_fields(schema, decode_on_device, ngram=None, transform_spec=None):
    """Fields whose codec decode should stop at the host staging half (stage 1)."""
    if not decode_on_device:
        return frozenset()
    if ngram is not None:
        raise ValueError("decode_on_device is not supported with NGram readers")
    if transform_spec is not None and not transform_spec.device \
            and transform_spec.func is not None:
        raise ValueError(
            "decode_on_device is not compatible with a host transform_spec: the "
            "transform would receive coefficient staging payloads, not decoded images. "
            "Use a device transform (TransformSpec(..., device=True)) or the "
            "DataLoader's device_transform instead."
        )
    fields = frozenset(
        name for name, f in schema.fields.items()
        if f.codec is not None and getattr(f.codec, "device_decodable", False)
    )
    if not fields:
        logger.warning(
            "decode_on_device=True but the read schema has no device-decodable codec "
            "fields (only CompressedImageCodec('jpeg') columns qualify); reading "
            "proceeds fully host-decoded"
        )
    return fields


def make_reader(dataset_url, schema_fields=None, reader_pool_type="thread", workers_count=4,
                results_queue_size=16, shuffle_row_groups=True, shuffle_row_drop_partitions=1,
                predicate=None, rowgroup_selector=None, num_epochs=1,
                cur_shard=None, shard_count=None, shard_seed=None, seed=None,
                cache_type="null", cache_location=None, cache_size_limit=None,
                cache_row_size_estimate=None, cache_extra_settings=None,
                transform_spec=None, filters=None, storage_options=None, filesystem=None,
                results_timeout_s=300.0, decode_on_device=False, wire_serializer=None,
                io_retries=None, io_retry_backoff_s=None, worker_respawns=None,
                io_options=None, recovery=None, provenance=None, watch=None,
                transport=None, tenant=None):
    """Open a petastorm(-tpu) dataset for per-row decoded reading (reference ~L60).

    ``schema_fields`` may be a list of names/regexes/UnischemaFields or an :class:`NGram`.

    ``decode_on_device=True`` routes device-decodable codec columns (JPEG) through the
    two-stage decode: workers run only the native entropy decode, and rows carry DCT
    coefficient staging payloads that :class:`petastorm_tpu.loader.DataLoader` finishes
    on device in one batched Pallas dispatch per batch. Consume such readers through the
    DataLoader (or call ``ops.decode_jpeg_batch`` yourself).

    ``io_retries`` / ``io_retry_backoff_s``: transient row-group read failures
    (connection resets, timeouts against object stores) are retried that many extra
    times (default 2) with jittered exponential backoff before propagating;
    ``io_retries=0`` restores the reference's fail-fast behavior (it has no retry —
    SURVEY.md §6).

    ``worker_respawns``: the process pool's elastic-recovery budget — a child that
    dies mid-item is replaced and its row group re-dispatched up to this many times
    (default 2; 0 = fail fast; the reference has no recovery).

    ``recovery``: a :class:`petastorm_tpu.recovery.RecoveryOptions` (or a dict of
    its fields) unifying the retry/backoff/deadline/respawn policy above — plus
    poison-item quarantine: with ``on_poison="quarantine"`` a plan item that
    repeatedly fails or kills workers is SKIPPED after ``poison_attempts``
    failures instead of crashing the job, surfaced in
    ``Reader.quarantine_report`` and charged to the checkpoint watermark so
    resume replays nothing and loses nothing. Explicitly-passed legacy kwargs
    (``io_retries=``/``io_retry_backoff_s=``/``worker_respawns=``) win over the
    struct. See docs/robustness.md.

    ``io_options``: the async read path's knobs (:class:`petastorm_tpu.io.IoOptions`
    or a dict of its fields) — row-group readahead (default on), adjacent-read
    coalescing, the in-memory decoded-row-group LRU (``memcache_bytes``), and
    work-stealing piece dispatch. See docs/performance.md "Read path".

    ``watch``: the mutable-dataset plane (ISSUE 11) —
    :class:`petastorm_tpu.dataset.WatchOptions`, a dict of its fields, or
    ``True`` for defaults. Stamps a per-file generation token
    (size+mtime+footer-crc) into every plan item and cache key, validates it
    on every read (a rewritten file quarantines as ``piece_rewritten``, a
    deleted one as ``piece_removed`` — under ``recovery.on_poison=
    "quarantine"``), and runs a watcher thread that discovers appended files
    mid-run and extends the epoch plan with checkpoint-watermark exactness.
    See docs/robustness.md "Mutable datasets".

    ``transport``: the process pool's wire (ISSUE 15) — ``'pipe'`` (default;
    today's unix-socket connection, byte-identical) or ``'tcp'`` (framed
    crc32-trailered loopback/LAN sockets with heartbeat half-open detection
    and jittered-backoff reconnect; a link death re-dispatches un-acked items
    through the quarantine path — exactly-once-or-quarantined survives the
    network). Also via ``PTPU_TRANSPORT``. See docs/robustness.md
    "The network fault model".

    ``tenant``: per-tenant accounting (ISSUE 18) — a bounded slug (or
    :class:`petastorm_tpu.obs.TenantContext`) that tags every shared-resource
    metric this reader's batches touch with a ``tenant=`` label; defaults to
    the ambient context / ``PTPU_TENANT`` env, absent ⇒ untagged (zero-cost).
    See docs/observability.md "Tenant accounting".
    """
    from petastorm_tpu.obs import tenant as _tenant_mod

    tenant_ctx = _tenant_mod.resolve(tenant)
    io_opts = IoOptions.normalize(io_options)
    _host_arena_early(io_opts)
    fs, path = get_filesystem_and_path_or_paths(dataset_url, storage_options, filesystem)
    stored_schema = get_schema(fs, path)

    pieces = load_row_groups(fs, path)
    watch_paths = {p.path for p in pieces}  # pre-pruning file set (watch plane)
    pieces = _apply_rowgroup_selector(fs, path, pieces, rowgroup_selector)
    stats_pieces = pieces  # pre-plan view: row-group stats still attached
    pieces, partition_info, filters = _plan_pieces(pieces, filters, predicate,
                                                   shard_count)
    watch = _resolve_watch(watch)
    if watch is not None:
        from petastorm_tpu.dataset.watch import stamp_generation_tokens

        pieces = stamp_generation_tokens(fs, pieces,
                                         footer_crc=watch.footer_crc)
    if partition_info:
        stored_schema = _schema_with_partitions(stored_schema, partition_info)

    ngram, read_schema = _resolve_ngram_schema(schema_fields, stored_schema,
                                               predicate)

    rec = RecoveryOptions.resolve(recovery, io_retries=io_retries,
                                  io_retry_backoff_s=io_retry_backoff_s,
                                  worker_respawns=worker_respawns)
    cache = make_cache(cache_type, cache_location, cache_size_limit,
                       cache_row_size_estimate, cache_extra_settings)
    cache = _build_read_funnel(
        cache, io_opts, num_epochs,
        tenant=tenant_ctx.tenant if tenant_ctx is not None else None)
    transform_spec = _maybe_compile_pipeline(transform_spec, read_schema, fs,
                                             stats_pieces, cache)
    final_schema = read_schema
    if transform_spec is not None and not transform_spec.device:
        final_schema = transform_schema(read_schema, transform_spec)
    device_fields = _resolve_device_fields(read_schema, decode_on_device, ngram,
                                           transform_spec)
    worker = PyDictWorker(
        fs, read_schema, stored_schema, predicate, transform_spec, cache,
        shuffle_row_drop_partitions, filters, seed if seed is not None else shard_seed,
        device_fields=device_fields, partition_info=partition_info,
        recovery=rec, io_options=io_opts,
        ngram=ngram, ngram_schema=final_schema if ngram is not None else None,
    )
    r = Reader(
        fs, path, final_schema, stored_schema, worker, pieces,
        num_epochs=num_epochs, shuffle_row_groups=shuffle_row_groups, seed=seed,
        cur_shard=cur_shard, shard_count=shard_count, shard_seed=shard_seed,
        shuffle_row_drop_partitions=shuffle_row_drop_partitions,
        reader_pool_type=reader_pool_type, workers_count=workers_count,
        results_queue_size=results_queue_size, is_batched_reader=False, ngram=ngram,
        results_timeout_s=results_timeout_s,
        wire_serializer=wire_serializer or "pickle",
        io_options=io_opts, recovery=rec,
        provenance=_prov.resolve(provenance), watch=watch,
        watch_paths=watch_paths, transport=transport, tenant=tenant_ctx,
    )
    r.transform_spec = transform_spec
    r.device_decode_fields = device_fields
    return r


def _resolve_watch(watch):
    """Factory-side normalization of the ``watch=`` kwarg (ISSUE 11)."""
    from petastorm_tpu.dataset.watch import WatchOptions

    return WatchOptions.normalize(watch)


def make_batch_reader(dataset_url_or_urls, schema_fields=None, reader_pool_type="thread",
                      workers_count=4, results_queue_size=16, shuffle_row_groups=True,
                      shuffle_row_drop_partitions=1, predicate=None, num_epochs=1,
                      cur_shard=None, shard_count=None, shard_seed=None, seed=None,
                      cache_type="null", cache_location=None, cache_size_limit=None,
                      cache_row_size_estimate=None, cache_extra_settings=None,
                      transform_spec=None, filters=None, storage_options=None,
                      filesystem=None, results_timeout_s=300.0, decode_on_device=False,
                      wire_serializer=None, io_retries=None, io_retry_backoff_s=None,
                      worker_respawns=None, io_options=None, recovery=None,
                      provenance=None, watch=None, transport=None, tenant=None):
    """Open ANY Parquet store for vectorized columnar batches (reference ~L200).

    ``decode_on_device``: see :func:`make_reader` — device-decodable codec columns come
    back as staging payloads for the DataLoader's batched on-device decode.

    ``io_retries`` / ``io_retry_backoff_s``: see :func:`make_reader` (transient
    read-failure retry with backoff; 0 = reference fail-fast behavior).

    ``recovery``: see :func:`make_reader` — the unified
    :class:`petastorm_tpu.recovery.RecoveryOptions` policy (retry/backoff/
    deadline, respawn budget, poison-item quarantine).

    ``io_options``: see :func:`make_reader` — readahead/coalesce/memcache/work
    stealing knobs for the async read path (docs/performance.md "Read path").

    ``watch``: see :func:`make_reader` — the mutable-dataset plane (ISSUE 11):
    generation-tokened plan items and cache keys, per-read validation, and a
    watcher thread that extends the plan with appended files mid-run (single
    dataset URL only).

    ``wire_serializer``: process-pool result wire format; defaults to ``"arrow"`` here
    (columnar batches ride Arrow IPC — reference ``ArrowTableSerializer`` parity) and
    ``"pickle"`` for :func:`make_reader` row payloads. ``"shm"`` selects the
    shared-memory slab wire (docs/performance.md) — batch results keep their Arrow
    framing but the frames travel through a slab ring instead of the socket
    (``"shm"``/``"shm-view"`` normalize to ``"shm-arrow"``/``"shm-arrow-view"``
    here). Thread/dummy pools share memory and ignore it.

    ``transport``: see :func:`make_reader` — the process pool's wire
    (``'pipe'`` default / ``'tcp'`` framed partition-tolerant sockets,
    ISSUE 15). The shm slab wire is bypassed over tcp (a network link cannot
    carry slab grants); payloads ride the framed socket wire instead.

    ``tenant``: see :func:`make_reader` — per-tenant accounting (ISSUE 18).
    """
    from petastorm_tpu.obs import tenant as _tenant_mod

    tenant_ctx = _tenant_mod.resolve(tenant)
    io_opts = IoOptions.normalize(io_options)
    _host_arena_early(io_opts)
    fs, path = get_filesystem_and_path_or_paths(
        dataset_url_or_urls, storage_options, filesystem
    )
    stored_schema = infer_or_load_unischema(fs, path if not isinstance(path, list) else path[0])

    paths = path if isinstance(path, list) else [path]
    pieces = []
    for p in paths:
        pieces.extend(load_row_groups(fs, p))
    watch_paths = {p.path for p in pieces}  # pre-pruning file set (watch plane)
    stats_pieces = pieces  # pre-plan view: row-group stats still attached
    pieces, partition_info, filters = _plan_pieces(pieces, filters, predicate,
                                                   shard_count)
    watch = _resolve_watch(watch)
    if watch is not None:
        from petastorm_tpu.dataset.watch import stamp_generation_tokens

        pieces = stamp_generation_tokens(fs, pieces,
                                         footer_crc=watch.footer_crc)
    if partition_info:
        stored_schema = _schema_with_partitions(stored_schema, partition_info)

    # NGram here is the TPU-first COLUMNAR path (no reference analog): windows are
    # assembled in-worker as flat 'offset/field' columns via one gather per
    # (offset, field); batches deliver as plain dicts (flat names cannot be
    # namedtuple attributes)
    ngram, read_schema = _resolve_ngram_schema(schema_fields, stored_schema,
                                               predicate)
    rec = RecoveryOptions.resolve(recovery, io_retries=io_retries,
                                  io_retry_backoff_s=io_retry_backoff_s,
                                  worker_respawns=worker_respawns)
    cache = make_cache(cache_type, cache_location, cache_size_limit,
                       cache_row_size_estimate, cache_extra_settings)
    cache = _build_read_funnel(
        cache, io_opts, num_epochs,
        tenant=tenant_ctx.tenant if tenant_ctx is not None else None)
    transform_spec = _maybe_compile_pipeline(transform_spec, read_schema, fs,
                                             stats_pieces, cache)
    final_schema = read_schema
    if transform_spec is not None and not transform_spec.device:
        final_schema = transform_schema(read_schema, transform_spec)
    device_fields = _resolve_device_fields(read_schema, decode_on_device, ngram,
                                           transform_spec=transform_spec)
    worker = ArrowWorker(
        fs, read_schema, stored_schema, predicate, transform_spec, cache,
        shuffle_row_drop_partitions, filters, seed if seed is not None else shard_seed,
        device_fields=device_fields, partition_info=partition_info,
        recovery=rec, io_options=io_opts,
        ngram=ngram,
    )
    r = Reader(
        fs, path, final_schema, stored_schema, worker, pieces,
        num_epochs=num_epochs, shuffle_row_groups=shuffle_row_groups, seed=seed,
        cur_shard=cur_shard, shard_count=shard_count, shard_seed=shard_seed,
        shuffle_row_drop_partitions=shuffle_row_drop_partitions,
        reader_pool_type=reader_pool_type, workers_count=workers_count,
        results_queue_size=results_queue_size, is_batched_reader=True, ngram=ngram,
        results_timeout_s=results_timeout_s,
        wire_serializer={"shm": "shm-arrow", "shm-view": "shm-arrow-view"}.get(
            wire_serializer, wire_serializer) or "arrow",
        io_options=io_opts, recovery=rec,
        provenance=_prov.resolve(provenance), watch=watch,
        watch_paths=watch_paths, transport=transport, tenant=tenant_ctx,
    )
    r.transform_spec = transform_spec
    r.device_decode_fields = device_fields
    return r


def make_service_reader(address, token, job, trainer=None, tenant=None,
                        recovery=None, credits=8, arena=True):
    """Attach to a :class:`petastorm_tpu.service.server.DataService` job
    (ISSUE 19): the disaggregated twin of :func:`make_batch_reader`. Instead
    of decoding locally, the returned
    :class:`~petastorm_tpu.service.client.ServiceReader` consumes the shared
    decode fleet's output — batched columnar delivery with the same
    ``state_dict()`` consumed-watermark checkpoint contract, pluggable into
    :class:`~petastorm_tpu.loader.DataLoader` unchanged.

    ``address``/``token`` come from the service
    (:meth:`~petastorm_tpu.service.server.DataService.trainer_address` /
    ``.token``); ``arena=True`` maps co-hosted payloads zero-copy out of the
    PR 17 host arena. See ``docs/service.md``.
    """
    from petastorm_tpu.service.client import ServiceReader

    return ServiceReader(address, token, job, trainer=trainer, tenant=tenant,
                         recovery=recovery, credits=credits, arena=arena)


def _resolve_partitions(pieces, filters):
    """Hive partitioning at plan time: typed :class:`~petastorm_tpu.partitions.PartitionInfo`
    from the piece paths + directory-level pruning of ``filters`` (reference
    ``pq.ParquetDataset(..., filters=)`` petastorm/reader.py ~L330). Returns
    ``(pieces, info-or-None, filters)`` where filter values on partition columns are
    coerced to the inferred column types (a string-valued filter against an int-typed
    partition must match, both here and in the workers' row-level mask); flat layouts
    pass through untouched."""
    from petastorm_tpu.partitions import (
        build_partition_info,
        normalize_filters,
        prune_pieces,
    )

    info = build_partition_info([p.partition_values or {} for p in pieces])
    if not info:
        return pieces, None, filters
    filters = normalize_filters(filters, info)
    pruned = prune_pieces(pieces, info, filters)
    if len(pruned) < len(pieces):
        logger.info("Hive partition pruning: %d of %d row groups scheduled",
                    len(pruned), len(pieces))
    return pruned, info, filters


def _prune_by_stats(pieces, filters):
    """Row-group statistics pruning (reference: ``pq.ParquetDataset`` consults parquet
    min/max before reading): drop pieces that NO or-clause of the DNF ``filters`` can
    match given their footer statistics. Conservative-correct: absent stats, unknown
    columns, and type mismatches all count as satisfiable — a piece is only dropped on
    a provable contradiction, and the row-level mask still runs for survivors.
    Parquet min/max exclude nulls, so ``!=``/``not in`` prune only groups with a
    recorded null count of zero (null rows MATCH those operators in the row mask).

    Stats survive on the returned pieces so pruning passes chain; the planner
    (``_plan_pieces``) strips them at the end — work items shipped to pool workers
    must not re-pickle per-column bounds."""
    if not pieces:
        return pieces
    if not filters:
        return pieces

    def term_unsat(stats, name, op, val):
        if not stats or name not in stats:
            return False
        mn, mx, nulls = stats[name]
        try:
            if op in ("=", "=="):
                return val < mn or val > mx
            if op == "!=":
                return nulls == 0 and bool(mn == mx == val)
            if op == "<":
                return mn >= val
            if op == "<=":
                return mn > val
            if op == ">":
                return mx <= val
            if op == ">=":
                return mx < val
            if op == "in":
                return all(v < mn or v > mx for v in val)
            if op in ("not in", "not-in"):
                if nulls != 0:
                    return False
                excluded = set(val)
                if bool(mn == mx):
                    return mn in excluded
                if isinstance(mn, (int, np.integer)) and isinstance(mx, (int, np.integer)):
                    # integer stats: unsatisfiable iff the excluded set covers every
                    # possible value in [mn, mx] (span bounded by len(excluded))
                    span = int(mx) - int(mn) + 1
                    return span <= len(excluded) and \
                        all((int(mn) + i) in excluded for i in range(span))
                return False
        except TypeError:  # mixed types (e.g. str filter vs bytes stats): no pruning
            return False
        return False

    kept = [
        p
        for p in pieces
        if any(not any(term_unsat(p.stats, *term) for term in clause)
               for clause in _dnf_clauses(filters))
    ]
    if len(kept) < len(pieces):
        logger.info("Row-group statistics pruning: %d of %d row groups scheduled",
                    len(kept), len(pieces))
    return kept


def _schema_with_partitions(schema, info):
    """Extend a stored/inferred schema with the partition-directory columns (they are
    not in any file's arrow schema but materialize as row values on read)."""
    from petastorm_tpu.partitions import partition_fields

    extra = [f for f in partition_fields(info, nullable=True)
             if f.name not in schema.fields]  # nullable: __HIVE_DEFAULT_PARTITION__ dirs
    if not extra:
        return schema
    return Unischema(schema._name, list(schema.fields.values()) + extra)


def _apply_rowgroup_selector(fs, path, pieces, rowgroup_selector):
    if rowgroup_selector is None:
        return pieces
    from petastorm_tpu.etl.rowgroup_indexing import get_row_group_indexes

    index_dict = get_row_group_indexes(fs, path)
    selected = rowgroup_selector.select_row_groups(index_dict)
    return [p for i, p in enumerate(pieces) if i in selected]
