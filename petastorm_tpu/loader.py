"""JAX ``DataLoader``: reader batches → globally-sharded ``jax.Array`` batches.

This is the TPU-native replacement for the reference's framework adapters
(petastorm/pytorch.py ``DataLoader``/``BatchedDataLoader`` ~L120/~L260 and
petastorm/tf_utils.py ``make_petastorm_dataset`` ~L350). Where the reference pays a
Python-callback + host-copy per training step (``tf.py_func`` / per-row torch collate), this
loader runs an async producer pipeline:

    reader (columnar numpy) → host re-batch [+ shuffling buffer] → background queue
        → ``jax.device_put`` with the consumer's ``Sharding`` (double/triple buffered)
        → optional jitted on-device transform (fused by XLA)

so the only per-step work on the critical path is a queue pop. Batches are *fixed-size*
(static shapes — XLA requirement); the remainder is dropped or padded per ``last_batch``.

Sharding contract (SURVEY.md §3.7): the loader accepts an arbitrary ``jax.sharding.Sharding``
for the batch. Data parallelism is the common case (batch axis over a mesh ``dp`` axis), but a
consumer running TP/SP can hand a sharding that splits feature/sequence axes and the loader
will lay batches out accordingly — this is the TPU-idiomatic superset of the reference's
``cur_shard``/``shard_count``. Under multi-process JAX each process's reader must already be
sharded (``cur_shard=jax.process_index()``); the loader assembles the global array with
``jax.make_array_from_process_local_data``.
"""
from __future__ import annotations

import collections
import functools
import itertools
import logging
import queue
import threading
import time
import weakref

import numpy as np

from petastorm_tpu.io.lease import (LeasedBatch, attach_leases, count_copy,
                                    detach_slab_views, take_leases)
from petastorm_tpu.parallel.mesh import batch_axis_shard_count
from petastorm_tpu.shuffle import BatchedRandomShufflingBuffer
from petastorm_tpu.trace import profiler_span, set_phase_sink, tallies
from petastorm_tpu.transfer import (_PACK_TABLE, DeviceTransfer, _is_device_dtype,
                                    column_sharding)
from petastorm_tpu.utils import stack_as_column

logger = logging.getLogger(__name__)

_SENTINEL = object()

#: per-process pipeline ids for health-scope namespacing: loaders SHARING one
#: HealthMonitor must not share heartbeat slots (itertools.count is GIL-atomic)
_pipeline_seq = itertools.count()


#: the phases of decode dispatch and H2D (``trace.phase`` names) -> their
#: ``PipelineStats`` totals
_PHASES = {"decode.pack": "decode_pack_s", "decode.put": "decode_put_s",
           "decode.launch": "decode_launch_s", "h2d.stage": "h2d_stage_s",
           "h2d.put": "h2d_put_s", "h2d.wait": "h2d_wait_s"}

#: the counters code below the loader adds to (``trace.count`` names)
_COUNTS = ("decode_batches", "decode_launches", "decode_real_blocks",
           "decode_canvas_blocks", "decode_host_rows", "crop_boxes")

#: the process-wide totals of the reader pool's stage-1 decode (``trace.tally``
#: names) the loader folds in
_TALLIES = ("stage1_images", "stage1_ns", "stage1_symbols", "stage1_fast_symbols")


class PipelineStats:
    """Cheap per-stage counters for the loader pipeline (SURVEY.md §6: the reference
    exports nothing; the north-star metric is device idle, which needs a stage split).

    All times are cumulative seconds since the last ``reset()``:

    - ``read_s``: producer time blocked on the reader (parquet IO + worker decode)
    - ``batch_s``: producer time re-batching/shuffling host rows
    - ``put_wait_s``: producer time blocked putting into a FULL host-batch queue
      (downstream backpressure — the producer outruns decode/transfer/step; the
      bottleneck analyzer's consumer-bound signal)
    - ``decode_s``: transfer-thread time in batched on-device codec decode dispatch
    - ``h2d_s``: transfer-thread time in ``device_put`` / global-array assembly
    - ``queue_wait_s``: transfer-thread time starved waiting on the host-batch queue
    - ``device_queue_wait_s``: consumer time starved waiting on the device-batch queue
      (the end-user-visible starvation — nonzero means the pipeline cannot keep the
      accelerator fed)

    The phases of those two (``trace.phase`` spans) split them: ``decode_pack_s``
    (stage-2 input stacked and narrowed on the host), ``decode_put_s`` (its
    ``device_put``), ``decode_launch_s`` (the stage-2 call, the flip, a reshard);
    ``h2d_stage_s`` (the pinned staging copy), ``h2d_put_s`` (the columns'
    ``device_put``s, or the staged slab's one put and the program that cuts it
    into columns), ``h2d_wait_s`` (the wait for the transfers before host
    buffers go back to their rings). ``h2d_bytes`` counts what both puts shipped.
    The wait blocks only on transfers that read a host buffer about to be
    recycled: the columns' ``device_put`` (from the staging slab or leased
    views) and any device program's input put from borrowed memory, never a
    program's output. ``h2d_unwaited_outputs`` counts the outputs of stage-2
    and of the pass-through inflate handed on without a host wait (one a
    batch for each decoded field; 0 without device decode).
    ``h2d_slab_puts`` counts the batches whose columns shipped as ONE transfer
    of their staged slab (at least two staged columns of one itemsize, dtypes
    JAX keeps as they are, one device), cut into the columns on the device
    by ``jit_ptpu_slab_split``; the wait then blocks on that one transfer.

    Device decode counts (``trace.count`` from the codec and ``ops/jpeg.py``):
    ``decode_batches`` (decoded columns of batches), ``decode_launches`` (stage-2
    programs launched: one a layout group, or one a canvas bucket),
    ``decode_real_blocks`` and ``decode_canvas_blocks`` (8x8 coefficient blocks
    the images hold, and the blocks those programs decode, padding included),
    ``decode_host_rows`` (rows decoded on the host instead: the pool's cv2
    fallbacks, among them the images no canvas holds in a row group of several
    sizes) and ``crop_boxes`` (crop
    boxes drawn under a ``random_resized`` or flipping policy).

    The reader pool's stage-1 decode, from the process-wide ``trace.tally``
    totals folded in at each reader delivery (so they count every reader of the
    process, and only the pool's threads in this process: a process pool's
    children count in their own): ``stage1_images`` (images entropy-decoded for
    device decode), ``stage1_ns`` (the decoding threads' CPU time in those calls,
    ``time.thread_time_ns``), ``stage1_symbols`` and ``stage1_fast_symbols``
    (Huffman symbols decoded, and those one table lookup resolved with their
    magnitude bits).

    Under ``DataLoader(pack=...)``: ``pack_s`` is producer time packing
    documents into rows (chunking, placement, the token copy); ``pack_slots``,
    ``pack_pad_slots`` and ``pack_chunks`` count the slots, the padding slots and
    the chunks of the batches it emitted.

    ``decode_unsharded_batches`` counts staged-decode dispatches that ran on a
    SINGLE device although the configured sharding cuts the batch axis across
    several — the undivisible-batch / local-mesh-derivation-failure fallbacks,
    and every batch a ``device_decode_resize`` crop decodes in canvases (that
    decode runs on one device). Nonzero on a pod means one chip is decoding for
    many; fix the batch size or the sharding.

    The ``shm_*`` fields mirror the process pool's shared-memory wire gauges
    (``Reader.wire_stats()``, refreshed per reader delivery; all zero on thread/
    dummy pools and socket wires): ``shm_slabs_in_flight`` (slabs currently out
    of the ring), ``shm_bytes`` (payload bytes that traveled through shared
    memory), ``shm_fallbacks`` (items that degraded to the socket wire —
    oversized payload or a starved ring), ``shm_acquire_wait_s`` (cumulative
    driver-thread wait for a free slab — sustained growth means the ring is
    undersized for the consumer's release cadence).

    The same totals are exported as the ``ptpu_pipeline_*`` metric families
    when the loader is built with ``metrics=`` (see
    :mod:`petastorm_tpu.obs.metrics`), and
    ``petastorm_tpu.obs.analyze.analyze_snapshot`` reads this snapshot shape
    directly (``DataLoader.bottleneck_report()``).
    """

    __slots__ = ("rows", "batches", "read_s", "batch_s", "put_wait_s",
                 "decode_s", "h2d_s",
                 "queue_wait_s", "device_queue_wait_s", "decode_unsharded_batches",
                 "shm_slabs_in_flight", "shm_bytes", "shm_fallbacks",
                 "shm_acquire_wait_s") + tuple(_PHASES.values()) + (
                     "h2d_bytes", "h2d_unwaited_outputs", "h2d_slab_puts", "pack_s",
                     "pack_slots", "pack_pad_slots", "pack_chunks") + _COUNTS + _TALLIES

    def __init__(self):
        self.reset()

    def reset(self):
        for field in self.__slots__:  # the ``*_s`` fields are seconds, the rest counts
            setattr(self, field, 0.0 if field.endswith("_s") else 0)

    def add_phase(self, name, seconds, nbytes=0):
        """A ``trace.phase`` of a stage ended: add it to its total (phases
        without one, e.g. the pool's, only count their bytes)."""
        field = _PHASES.get(name)
        if field is not None:
            setattr(self, field, getattr(self, field) + seconds)
        self.h2d_bytes += nbytes

    def add_count(self, name, n):
        """A ``trace.count`` below the loader: add ``n`` to counter ``name``."""
        if name in _COUNTS:
            setattr(self, name, getattr(self, name) + n)

    def fold_tallies(self, seen):
        """Add what the process-wide stage-1 totals (``trace.tallies``) grew by
        since ``seen``, the caller's last reading, which is brought up to date."""
        now = tallies()
        for name in _TALLIES:
            grown = now.get(name, 0) - seen.get(name, 0)
            if grown:
                setattr(self, name, getattr(self, name) + grown)
        seen.update(now)

    def snapshot(self):
        return {field: round(getattr(self, field), 4) if field.endswith("_s")
                else getattr(self, field) for field in self.__slots__}

    def update_wire(self, wire_stats):
        """Fold the pool's shm gauges (``Reader.wire_stats()`` dict) in."""
        if not wire_stats:
            return
        self.shm_slabs_in_flight = wire_stats.get("shm_slabs_in_flight", 0)
        self.shm_bytes = wire_stats.get("shm_bytes", 0)
        self.shm_fallbacks = wire_stats.get("shm_fallbacks", 0)
        self.shm_acquire_wait_s = wire_stats.get("shm_acquire_wait_s", 0.0)


#: the loader's stages: name -> (PipelineStats field, latency-histogram stage,
#: provenance site, tenant resource). One vocabulary for the ``trace=`` dump,
#: the histograms, provenance, chaos sites and the profiler (``ptpu.<name>``)
_STAGES = {
    "reader.next": ("read_s", "read", None, None),
    "batch.form": ("batch_s", "batch", None, None),
    "pack": ("pack_s", "pack", None, None),
    "wait.host_queue_put": ("put_wait_s", "host_queue_put", "loader.host_queue_put",
                            None),
    "wait.host_queue": ("queue_wait_s", "host_queue_wait", None, None),
    "decode.dispatch": ("decode_s", "decode", "loader.decode", "decode_s"),
    "decode.device_inflate": (None, "device_inflate", "decode.device_inflate", None),
    "h2d.transfer": ("h2d_s", "h2d", "loader.h2d", None),
    "wait.device_queue": ("device_queue_wait_s", "device_queue_wait", None, None),
}
_OBS_STAGES = tuple(v[1] for v in _STAGES.values())


class _Stage:
    """One occurrence of a loader stage, timed by one ``perf_counter`` pair
    (``t0``, ``dt``) that reaches every sink the loader has on: its
    ``PipelineStats`` field, the ``trace=`` recorder, the stage histogram, the
    flight ring, provenance (on ``bp`` when given, else on the batch in
    transfer), the tenant ledger, and ``ptpu.<name>`` on the JAX profiler's
    clock with ``args`` (``batch=<host-batch number>``) as its metadata, and
    more through :meth:`set_metadata`. While open, the ``trace.phase`` spans on
    its thread add to the stats' phases."""

    __slots__ = ("loader", "name", "bp", "span", "entered", "t0", "dt")

    def __init__(self, loader, name, bp=None, **args):
        self.loader, self.name, self.bp = loader, name, bp
        self.span = profiler_span("ptpu." + name, **args)

    def set_metadata(self, **args):
        """Add ``args`` to the open profiler span (a no-op without JAX)."""
        if self.entered is not None:
            self.entered.set_metadata(**args)

    def __enter__(self):
        self.entered = self.span.__enter__()
        set_phase_sink(self.loader.stats)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dt = dt = time.perf_counter() - self.t0
        set_phase_sink(None)
        self.span.__exit__(*exc)
        loader, name = self.loader, self.name
        field, stage, site, resource = _STAGES[name]
        if field is not None:
            setattr(loader.stats, field, getattr(loader.stats, field) + dt)
        if loader._trace is not None:
            loader._trace.add(name, self.t0, dt)
        if loader._obs is not None:
            loader._obs.observe(stage, dt)
        scope = loader._health_scope
        if scope is not None:
            scope.flight.record("span", name=name, dur_s=round(dt, 6))
        rec = loader._prov_rec
        if rec is not None and site is not None:
            if self.bp is not None:
                rec.batch_span(self.bp, site, self.t0, dt)
            else:
                rec.transfer_span(site, self.t0, dt)
        if resource is not None and loader.tenant_context is not None:
            from petastorm_tpu.obs import tenant as _tenant_mod

            _tenant_mod.charge(resource, dt, label=loader.tenant_context.tenant)
        return False


class _LoaderObs:
    """Pre-resolved metric handles for one loader's hot path (ISSUE 3).

    Built only when ``DataLoader(metrics=...)`` was requested, so the disabled
    path stays one ``is None`` check per site (the ``trace.py`` contract). Holds
    one log-bucketed latency histogram per pipeline stage
    (``ptpu_pipeline_stage_seconds{stage=...}``) and registers two pull
    collectors: the ``PipelineStats`` totals + live queue depths as
    ``ptpu_pipeline_*``, and ``Reader.wire_stats()`` (slab-ring gauges) as
    ``ptpu_wire_*`` — the migration of the pre-existing ad-hoc gauges onto
    named metric families, with their hot paths unchanged.

    One metrics-enabled loader per registry at a time: the family names carry
    no per-loader label, so two live pipelines on ONE registry would merge
    their stage histograms and clobber each other's collector keys. Give each
    concurrent loader its own ``MetricsRegistry`` (an exporter can serve
    several registries to distinct files).

    The loader is held through a WEAK reference: collectors survive in the
    registry when a caller skips the context-manager teardown, but a
    garbage-collected pipeline stops exporting (and is not kept alive by the
    registry) instead of freezing its last gauges into every future snapshot.
    """

    def __init__(self, registry, loader):
        self.registry = registry
        self._hists = {
            stage: registry.histogram(
                "ptpu_pipeline_stage_seconds",
                help="per-occurrence pipeline stage latency (seconds)",
                stage=stage)
            for stage in _OBS_STAGES
        }
        self._handles = [registry.register_collector(
            "pipeline", self._collect_pipeline)]
        health = getattr(loader, "_health", None)
        if health is not None:
            # health layer (ISSUE 5): heartbeat ages + stalled flags + stall
            # total export as ptpu_health_* (the monitor's lifetime is tied to
            # the loader's, and close() unregisters the collector with the rest)
            self._handles.append(registry.register_collector(
                "health", health.collect))
        self._loader_ref = weakref.ref(loader)
        wire_stats_fn = getattr(loader.reader, "wire_stats", None)
        if wire_stats_fn is not None:
            # weak like the loader: the registry must not pin a dead reader
            wire_ref = weakref.WeakMethod(wire_stats_fn)
            self._handles.append(registry.register_collector(
                "wire", lambda: (wire_ref() or dict)()))
        io_stats_fn = getattr(loader.reader, "io_stats", None)
        if io_stats_fn is not None:
            # async read path (ISSUE 4): readahead hit/miss/pending/bytes,
            # memcache, dispatch steals — live gauges as ptpu_io_* families
            io_ref = weakref.WeakMethod(io_stats_fn)
            self._handles.append(registry.register_collector(
                "io", lambda: (io_ref() or dict)()))
        prov = getattr(loader, "_prov_rec", None)
        if prov is not None:
            # provenance plane (ISSUE 10): item/batch counts + per-site
            # critical-path self seconds as ptpu_prov_* (rendered by the
            # petastorm-tpu-stats attribution panel)
            prov_ref = weakref.ref(prov)
            self._handles.append(registry.register_collector(
                "prov", lambda: (lambda r: r.summary() if r is not None
                                 else {})(prov_ref())))

    def add_collector(self, prefix, fn):
        """Register one more pull collector whose lifetime follows this
        loader's (unregistered with the rest at ``close()``)."""
        self._handles.append(self.registry.register_collector(prefix, fn))

    def observe(self, stage, dur):
        self._hists[stage].observe(dur)

    def stage_histograms(self):
        return dict(self._hists)

    def reset_stage_histograms(self):
        """Re-anchor the stage percentiles to a fresh window (benchmarks call
        this beside ``PipelineStats.reset()`` so the bottleneck report's p50/
        p90/p99 cover the measured window, not warmup/compile)."""
        for hist in self._hists.values():
            hist.reset()

    def _collect_pipeline(self):
        loader = self._loader_ref()
        if loader is None:
            return {}
        out = dict(loader.stats.snapshot())
        q = loader._queue
        dq = loader._dev_queue
        out["host_queue_depth"] = q.qsize() if q is not None else 0
        out["device_queue_depth"] = dq.qsize() if dq is not None else 0
        return out

    def close(self):
        """Unregister the pull collectors (loader ``__exit__``): a torn-down
        pipeline must stop contributing stale families to exports."""
        handles, self._handles = self._handles, []
        for handle in handles:
            self.registry.unregister_collector(handle)


def _validate_decode_resize(resize, device_fields):
    """Normalize/validate ``device_decode_resize`` at construction: a misspelled dict
    key or a malformed target must fail HERE, not silently no-op and resurface later
    as a mixed-size error telling the user to pass the option they already passed.
    Returns a :class:`~petastorm_tpu.ops.crop.CropPolicy`, a dict of them by field,
    or None."""
    if resize is None:
        return None
    if not device_fields:
        raise ValueError(
            "device_decode_resize was given but the reader has no device-decoded "
            "fields — open it with decode_on_device=True (and an image-codec "
            "column) for the on-device resize to apply")
    from petastorm_tpu.ops.crop import parse_policy

    known = set(device_fields or ())
    if isinstance(resize, dict) and not ("size" in resize and "size" not in known):
        unknown = set(resize) - known
        if unknown:
            raise ValueError(
                "device_decode_resize names %s, but the reader's device-decoded "
                "fields are %s (is decode_on_device=True set, and are the names "
                "spelled right?)" % (sorted(unknown), sorted(known)))
        return {k: parse_policy(v, "[%r]" % k) for k, v in resize.items()}
    return parse_policy(resize)


class _HostBatcher:
    """Accumulates columnar chunks and cuts exact fixed-size batches (static shapes)."""

    def __init__(self, batch_size, shuffling_queue_capacity=0, seed=None):
        self.batch_size = batch_size
        if shuffling_queue_capacity and shuffling_queue_capacity > 0:
            self._buffer = BatchedRandomShufflingBuffer(
                shuffling_queue_capacity,
                min_after_retrieve=min(shuffling_queue_capacity // 2, shuffling_queue_capacity - 1),
                batch_size=batch_size,
                seed=seed,
            )
            self._shuffling = True
        else:
            self._buffer = None
            self._shuffling = False
            self._pending = {}  # {name: deque of (array, offset)} — remainder stays put
            self._pending_rows = 0

    # -- non-shuffling path: chunk deque, O(batch) per cut ------------------------------
    #
    # Batches are assembled from whole/partial chunk VIEWS; the remainder is tracked as
    # an offset into the head chunk instead of re-sliced into a fresh array every cut
    # (the previous whole[batch_size:] copy was O(rowgroup^2/batch) bytes per row group).
    #
    # Lease retention (ISSUE 6): a chunk delivered with a lease (zero-copy slab views
    # from a view-mode wire) records that lease on every per-column entry; each batch
    # cut from leased chunks RETAINS the contributing leases (a LeasedBatch rides
    # them downstream), and the batcher's own hold drops as chunks drain — this is
    # what replaced the per-delivery _detach_slab_views copy-out.

    def _plain_add(self, columns, lease=None):
        n = None
        for name, arr in columns.items():
            entry = [arr, 0, lease]
            if lease is not None:
                lease.retain()  # one hold per column entry
            self._pending.setdefault(name, []).append(entry)
            n = len(arr)
        if lease is not None:
            lease.release()  # the ownership ref handed in: now held per entry
        if n is not None:
            self._pending_rows += n

    def _cut_one(self, take):
        merged = {}
        batch_leases = {}
        drained = []
        for name, chunks in self._pending.items():
            parts = []
            need = take
            while need > 0:
                entry = chunks[0]
                arr, off, lease = entry
                if lease is not None:
                    batch_leases[id(lease)] = lease
                avail = len(arr) - off
                if avail > need:
                    parts.append(arr[off:off + need])
                    entry[1] = off + need
                    need = 0
                else:
                    parts.append(arr[off:] if off else arr)
                    chunks.pop(0)
                    if lease is not None:
                        drained.append(lease)
                    need -= avail
            merged[name] = parts[0] if len(parts) == 1 else _concat(parts)
        self._pending_rows -= take
        if batch_leases:
            # retain for the batch BEFORE dropping the drained entries' holds:
            # a drained entry may hold the last reference, and releasing it
            # first would return the slab under the batch's feet
            merged = attach_leases(
                merged, [lease.retain() for lease in batch_leases.values()])
        for lease in drained:
            lease.release()
        return merged

    def _plain_cut(self, final=False):
        out = []
        while self._pending_rows >= self.batch_size:
            out.append(self._cut_one(self.batch_size))
        if final and self._pending_rows > 0:
            out.append(self._cut_one(self._pending_rows))
        return out

    # -- public -----------------------------------------------------------------------

    def add(self, columns, lease=None):
        """Feed one columnar chunk; returns list of ready full-size batches.
        ``lease`` (ownership transferred in) marks the chunk's arrays as views
        into lease-backed buffers — only supported on the non-shuffling path
        (the shuffling buffer holds rows indefinitely, so its feed is detached
        by the producer instead)."""
        if not self._shuffling:
            self._plain_add(columns, lease)
            return self._plain_cut()
        if lease is not None:  # defensive: the producer never does this
            lease.release()
        ready = []
        self._buffer.add_many(columns)
        while self._buffer.can_retrieve:
            ready.append(self._buffer.retrieve())
        return ready

    def finish(self):
        """Flush remaining rows as (possibly short) final batches."""
        if not self._shuffling:
            return self._plain_cut(final=True)
        self._buffer.finish()
        ready = []
        while self._buffer.can_retrieve:
            ready.append(self._buffer.retrieve())
        return ready

    def close(self):
        """Drop the batcher's holds on any still-pending leased chunks (producer
        teardown mid-epoch: rows that never formed a batch)."""
        if self._shuffling:
            return
        for chunks in self._pending.values():
            for _arr, _off, lease in chunks:
                if lease is not None:
                    lease.release()
        self._pending.clear()
        self._pending_rows = 0


def _batch_row_count(batch):
    """Rows in a yielded batch (leading dim of the first column; 0 when empty)."""
    if not batch:
        return 0
    return int(len(next(iter(batch.values()))))


def _materialize_passthrough(batch, cause=None):
    """Inflate any compressed-page pass-through columns IN PLACE via the host
    reference decode (ISSUE 14). In-place keeps a ``LeasedBatch``'s identity
    and leases intact (pass-through buffers are owned bytes, never slab
    views). ``cause`` names the degradation to count when this seam is a
    FALLBACK (shuffling buffers, pad tails) rather than the designed host
    path (host-only delivery, loader-less readers pass ``None``)."""
    names = [name for name, v in batch.items()
             if getattr(v, "is_passthrough", False)]
    if not names:
        return batch
    if cause is not None:
        from petastorm_tpu.obs.log import degradation

        degradation(cause, "pass-through column(s) %s inflated on host at a "
                    "buffering seam; the device inflate stage was bypassed",
                    names)
    for name in names:
        batch[name] = batch[name].materialize()
    return batch


def _batch_valid_rows(batch):
    """Rows the READER actually delivered in this batch: under ``last_batch='pad'``
    the tail batch repeats its final row up to ``batch_size`` with a ``__valid__``
    mask, and counting the padding would advance the consumer checkpoint watermark
    past the producer's delivered-row log (ADVICE r5 loader.py:846 — harmless at
    the tail today, wrong the moment padding ever happens mid-stream)."""
    if not batch:
        return 0
    valid = batch.get("__valid__")
    if isinstance(valid, np.ndarray) and valid.dtype == np.bool_:
        return int(valid.sum())
    return _batch_row_count(batch)


def _concat(chunks):
    chunks = [c for c in chunks if len(c)]
    if not chunks:
        return np.empty((0,))
    if len(chunks) == 1:
        return chunks[0]
    if any(getattr(c, "is_passthrough", False) for c in chunks):
        from petastorm_tpu.io.pagedec import PassthroughColumn

        if all(getattr(c, "is_passthrough", False) for c in chunks):
            # window chaining, not a copy: the batch keeps riding raw pages
            return PassthroughColumn.concat(chunks)
        # mixed chunk types for one column (a per-chunk fallback mid-epoch):
        # the decoded form is the common denominator
        chunks = [c.materialize() if getattr(c, "is_passthrough", False)
                  else c for c in chunks]
    if any(c.dtype == object for c in chunks):
        out = np.empty(sum(len(c) for c in chunks), dtype=object)
        pos = 0
        for c in chunks:
            out[pos:pos + len(c)] = c
            pos += len(c)
    else:
        out = np.concatenate(chunks, axis=0)
    count_copy("loader_concat", out.nbytes)
    return out


def _release_leases(batch):
    """Release every lease a batch carries (no-op for plain dicts): the tidy
    path for batches that die inside the pipeline — dropped tails, stopped
    deliveries, queue drains — so teardown never strands a slab hold until GC
    (which would count as ``ptpu_lease_leaked_total``)."""
    for lease in take_leases(batch):
        lease.release()


def _flatten_ngram_window(window):
    """{offset: row} NGram window → one flat {'offset/field': value} row.

    The reference's torch collate nests tensors per offset; for the device path a
    FLAT naming keeps every loader feature working unchanged — per-field shardings,
    ``pad_shapes``, masks, and device transforms all key by ``'0/image'``-style
    names. Consumers regroup with one dict comprehension."""
    flat = {}
    for off, row in window.items():
        row = row._asdict() if hasattr(row, "_asdict") else row
        for name, value in row.items():
            flat["%s/%s" % (off, name)] = value
    return flat


def _rows_to_columns(rows, object_fields=()):
    """Row dicts/namedtuples → columnar numpy dict (per-row ``make_reader`` path).

    ``object_fields`` are forced to object dtype: device-decode staging columns may mix
    JpegPlanes payloads with host-fallback ndarrays across rows, and letting np.asarray
    pick a per-batch layout would break downstream concatenation."""
    if not rows:
        return {}
    first = rows[0]
    if hasattr(first, "_asdict"):
        rows = [r._asdict() for r in rows]
    names = rows[0].keys()
    return {
        name: stack_as_column([r[name] for r in rows], force_object=name in object_fields)
        for name in names
    }


class DataLoader:
    """Iterable of batches: ``{field: jax.Array}`` (device fields) laid out per ``sharding``.

    Parameters
    ----------
    reader : petastorm_tpu.reader.Reader
        Batch reader (columnar) or per-row reader (rows are stacked host-side).
        A :class:`petastorm_tpu.service.client.ServiceReader` (ISSUE 19 —
        :func:`petastorm_tpu.reader.make_service_reader`) plugs in here
        unchanged: batches then come from a shared decode fleet instead of a
        local pool, with the same batch/checkpoint semantics.
    batch_size : int
        GLOBAL batch size: rows per yielded batch across all processes. Under
        multi-process JAX with a ``NamedSharding`` whose batch axis spans processes,
        each process cuts only its local share (``batch_size / batch-shards ×
        locally-owned shard positions``) and the global array is assembled from the
        process-local parts; with one process (or a replicated batch axis) local ==
        global.
    sharding : jax.sharding.Sharding, optional
        Layout for yielded arrays. Default: single-device placement on the default device.
    shuffling_queue_capacity : int
        >0 enables a host-side row shuffling buffer (reference ``shuffling_queue_capacity``).
    last_batch : {"drop", "pad", "partial"}
        Remainder policy. ``drop`` (default) keeps shapes static; ``pad`` repeats final rows
        up to ``batch_size`` and adds a boolean ``__valid__`` mask column; ``partial`` yields
        the short batch (host numpy only fields keep working; device arrays get a new shape —
        triggers one extra XLA compile).
    device_transform : callable, optional
        Jittable ``fn(batch) -> batch`` applied on device after transfer (augment/normalize —
        XLA fuses it into the step). Defaults to ``reader.transform_spec`` when that was
        declared ``device=True``. A two-argument ``fn(batch, key) -> batch`` receives a
        fresh ``jax.random`` key per batch (folded from ``seed`` and a batch counter) —
        the hook for random augmentation (crop/flip) on device.
    prefetch : int
        Device batches kept in flight (double/triple buffering). 0 disables (debug).
    to_device : bool
        False yields host numpy dicts (CPU-only consumers, tests, torch adapter).
    device_shuffle_capacity : int
        >0 enables the HBM-resident exchange shuffle
        (:class:`petastorm_tpu.ops.device_shuffle.DeviceShuffleBuffer`): after
        transfer, each batch swaps into a device ring of ~this many rows and the
        displaced rows are delivered (exactly-once, ~capacity decorrelation window,
        one fused gather+scatter per batch — zero host work). Requires every
        delivered column to be device-resident (no strings); composes with
        ``shuffling_queue_capacity`` (host pre-shuffle) and ``device_transform``
        (applied to the shuffled output). Capacity is rounded up to a batch multiple.
    pad_shapes : dict, optional
        Ragged-field policy (SURVEY.md §8 hard part #2): ``{field: max_shape}`` pads
        every row of a ragged tensor field up to ``max_shape`` (zeros) and adds a
        boolean ``<field>__mask`` column marking the valid region, so the column
        reaches the device with a static shape. Rows exceeding the declared max raise.
        Ragged tensor fields WITHOUT a declared max raise at transfer time.
    pack : dict, optional
        Sequence packing for language-model pretraining:
        ``{"field": "input_ids", "length": 8192}`` packs the documents of a
        ragged integer list column (one document a row) into ``[batch_size,
        length]`` rows; ``batch_size`` then counts packed rows. The reader must
        deliver only that field (``schema_fields=[field]``). Rules: documents are
        taken in delivered order; an empty one gives no chunk; one of ``n``
        tokens gives ``ceil(n / length)`` chunks, in order, each ``length``
        tokens except the last (no token is dropped); each chunk goes to the
        lowest-index row of the batch being formed that has room for it, and
        when no row has room the batch is emitted and the chunk starts the next
        one, so a part-filled batch carries across reader deliveries, row groups
        and epochs. At the end of a finite stream ``last_batch`` applies to the
        part-filled batch: ``drop`` drops it unless every row holds a chunk,
        ``pad`` adds empty rows marked false in ``__valid__``, ``partial`` yields
        its rows that hold a chunk. Each batch has three ``[rows, length]`` int32
        columns: ``<field>`` (tokens, 0 in padding), ``<field>_segment_ids`` (a
        row's chunks numbered 1, 2, ... in placement order, 0 in padding) and
        ``<field>_positions`` (0 .. len-1 within each chunk, 0 in padding). The
        producer thread packs (the ``pack`` stage) and ships the tokens and a
        small chunk table; the device program ``jit_ptpu_pack_layout`` derives
        the segment ids and positions after the transfer. Raises with
        ``shuffling_queue_capacity``, ``device_shuffle_capacity``, ``pad_shapes``
        on the field and a batch split over processes, and ``state_dict()``
        raises: the part-filled batch is not checkpointed yet.
    device_decode_resize : tuple, dict, optional
        The delivered size of device-decoded image fields (``decode_on_device=True``
        readers), so that a store of mixed sizes batches in one static shape:
        ``(h, w)`` resizes each whole image; a policy dict such as ``{"size": [224,
        224], "crop": "random_resized", "scale": [0.08, 1.0], "ratio": [0.75,
        1.3333], "flip": 0.5}`` crops first, as torchvision's ``RandomResizedCrop``
        (10 attempts, then the center crop) and a random horizontal flip do
        (keys: ``size``; ``crop``, ``"whole"`` or ``"random_resized"``;
        ``scale``; ``ratio``; ``flip``, a probability). Either may be given per
        field, ``{field: ...}``. Boxes are drawn on the host from each image's
        header size, keyed by the loader's ``seed`` (else the reader's) and the
        batch number, and delivered beside the image as ``<field>_box`` (``(n,
        5)`` int32: y0, x0, h, w, flip); the image decodes in a canvas bucket
        and is cropped, resized (bilinear, ``cv2.resize`` INTER_LINEAR's
        sampling) and flipped on the device (``jit_ptpu_jpeg_crop_resize``), on
        one device whatever the ``sharding`` (``decode_unsharded_batches``). A
        batch already at ``(h, w)`` decodes as without the option.
    trace : petastorm_tpu.trace.TraceRecorder, optional
        Records every pipeline stage (reader fetch, batch formation, decode
        dispatch, H2D, queue waits) as chrome-trace spans — the per-span view of
        the totals in ``stats``; ``tracer.dump(path)`` loads in ``chrome://tracing``
        / Perfetto. Default None = zero overhead.
    metrics : petastorm_tpu.obs.MetricsRegistry or True, optional
        Export the pipeline onto the metrics registry (``True`` = the
        process-wide default registry): per-stage latency histograms
        (``ptpu_pipeline_stage_seconds{stage=...}``, log-bucketed p50/p90/p99),
        the ``PipelineStats`` totals + live queue depths as ``ptpu_pipeline_*``,
        and the pool wire gauges as ``ptpu_wire_*`` — what
        ``petastorm_tpu.obs.export`` exporters and ``petastorm-tpu-stats``
        read. The families carry no per-loader label, so run at most ONE
        metrics-enabled loader per registry at a time (concurrent train + eval
        loaders: one private ``MetricsRegistry`` each). Default None =
        disabled, one ``is None`` check per stage site.
    health : True, petastorm_tpu.obs.health.HealthOptions or HealthMonitor, optional
        Active stall monitoring (ISSUE 5): every pipeline actor (this loader's
        producer and transfer thread, the reader's executor workers and
        readahead IO threads, process-pool children) stamps a heartbeat, and a
        watchdog daemon writes a structured **flight record** (driver + child
        stacks, queue depths, recent events) when a busy actor misses its
        threshold — backpressure waits never count as stalls. ``True`` =
        defaults; a :class:`~petastorm_tpu.obs.health.HealthOptions` tunes
        thresholds/escalation (escalation ``"raise"`` delivers
        :class:`petastorm_tpu.errors.StallError` to the consumer so training
        fails fast instead of hanging a TPU slice); an existing
        :class:`~petastorm_tpu.obs.health.HealthMonitor` is shared (the caller
        owns its lifecycle). ``PTPU_HEALTH=1`` enables the defaults without
        code changes. Default None = disabled, one ``is None`` check per
        site. ``DataLoader.health_report()`` works whenever it is on; with
        ``metrics=`` heartbeat ages also export as ``ptpu_health_*`` families.
    staging : None, bool or int, optional
        Pinned-host H2D staging (ISSUE 6): the transfer thread copies each
        batch's device-bound columns into a page-locked slab ring
        (:class:`petastorm_tpu.io.staging.PinnedStagingPool`) and launches
        ``device_put`` from there, so the DMA engine reads page-locked memory
        instead of pageable numpy (no runtime-side pinning/bounce per batch).
        Default ``None`` = auto: enabled on accelerator backends (TPU/GPU),
        off on the CPU backend where ``device_put`` may alias host memory and
        the extra staging copy buys nothing. ``True`` forces it on (still
        refused, with a ``staging_aliasing`` degradation, on a backend whose
        ``device_put`` aliases — recycled slabs would corrupt delivered
        arrays); ``False`` disables; an ``int`` forces it on with that slab
        size in bytes (otherwise sized from the first staged batch).
    provenance : True or petastorm_tpu.obs.provenance.ProvenanceRecorder, optional
        Causal per-item provenance (ISSUE 10): every dispatched row group
        accumulates ``(site, t_start, t_end, pid)`` spans and annotations
        (cache tier served from, hedges fired/won, retries, quarantine)
        through the whole pipeline — pool children included, via the
        result-header piggyback — and each delivered batch knows its
        contributing items. ``DataLoader.batch_provenance()`` returns the
        latest batch's record; ``DataLoader.attribution_report()`` folds the
        window into a critical-path step-time attribution (which SITE owns
        the p99 batch). ``True`` builds a recorder; pass an existing
        :class:`~petastorm_tpu.obs.provenance.ProvenanceRecorder` to share
        one. One provenance-enabled loader per process at a time (the item
        hooks are a process-global plane, like the chaos plan).
        ``PTPU_PROVENANCE=1`` enables it without code changes. Default None =
        disabled, one module-global ``is None`` check per site. Batch↔item
        attribution is unavailable under shuffling (rows decorrelate from row
        groups); per-item records still collect.
    slos : sequence of petastorm_tpu.obs.slo.SloSpec, or an SloEngine, optional
        Temporal SLO watching (ISSUE 12; requires ``metrics=``): the specs
        are evaluated against the registry's windowed time-series on the
        sampling cadence (a :class:`petastorm_tpu.obs.export.Reporter`
        flushing this registry, or explicit ``registry.sample_timelines()``
        calls). Debounced breaches fire ``cause=slo_breach`` degradation
        events mirrored into live flight recorders, and — when the loader
        also has ``provenance=`` — each alert carries an
        ``attribution_report()`` snapshot naming the culprit site. Read
        alerts from ``loader.slo_alerts()`` / ``loader.slo_engine``; pass a
        pre-built :class:`petastorm_tpu.obs.slo.SloEngine` to add anomaly
        watches or share an engine. Zero hot-path cost — evaluation happens
        on the sampler thread only.
    controller : True, petastorm_tpu.control.ControlOptions or Controller, optional
        Closed-loop self-tuning (ISSUE 13; requires ``metrics=``): a
        :class:`~petastorm_tpu.control.Controller` rides the same window
        cadence as the SLO engine and retunes the reader's LIVE knobs
        through the sanctioned :class:`~petastorm_tpu.control.KnobSet`
        seam — readahead depth/bytes, ranged-GET pool width, hedge
        quantile, mem-tier budget, disk admission, worker-fleet size
        (shrink drains, never kills mid-item). Declarative rules with
        hysteresis, debounce, per-knob cooldowns, step limits and a global
        revert-and-freeze no-gain guard (the anti-oscillation contract —
        docs/performance.md). With ``provenance=`` the rules read the
        attribution snapshot, so actuations are triggered by (and logged
        with) the culprit SITE. Decisions are ``cause=ctl_actuate``/
        ``ctl_revert``/``ctl_freeze`` degradation events plus
        ``ptpu_ctl_*`` families; read them from ``loader.ctl_decisions()``
        / ``loader.controller``. Zero hot-path cost.
    """

    def __init__(self, reader, batch_size, sharding=None, shuffling_queue_capacity=0,
                 seed=None, last_batch="drop", device_transform=None, prefetch=2,
                 to_device=True, host_queue_size=8, pad_shapes=None,
                 device_shuffle_capacity=0, device_decode_resize=None, trace=None,
                 metrics=None, health=None, staging=None, provenance=None,
                 slos=None, controller=None, tenant=None, pack=None):
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        #: per-tenant accounting (ISSUE 18): explicit tenant= wins, else the
        #: reader's resolved context, else ambient/PTPU_TENANT; None ⇒ untagged
        from petastorm_tpu.obs import tenant as _tenant_mod

        self.tenant_context = _tenant_mod.resolve(tenant, env_default=False) \
            if tenant is not None else None
        if self.tenant_context is None:
            self.tenant_context = getattr(reader, "tenant_context", None)
        if self.tenant_context is None:
            self.tenant_context = _tenant_mod.current()
        if last_batch not in ("drop", "pad", "partial"):
            raise ValueError("last_batch must be drop|pad|partial, got %r" % last_batch)
        if device_shuffle_capacity and not to_device:
            raise ValueError("device_shuffle_capacity requires to_device=True "
                             "(the ring lives in device memory)")
        self.reader = reader
        self.batch_size = int(batch_size)
        #: rows THIS process cuts per batch (== batch_size unless the sharding's batch
        #: axis spans multiple processes — ADVICE r1: batch_size is documented global)
        self.local_batch_size = _resolve_local_batch(self.batch_size, sharding)
        self.sharding = sharding
        self.last_batch = last_batch
        self.prefetch = int(prefetch)
        self.to_device = to_device
        self._seed = seed
        self._shuffling_queue_capacity = shuffling_queue_capacity
        self._host_queue_size = host_queue_size
        self._pad_shapes = dict(pad_shapes) if pad_shapes else {}
        # (h, w), a crop policy, or {field: either}: the on-device crop and resize
        # of device-decoded image columns, so mixed-size stores batch in one shape
        resize = _validate_decode_resize(
            device_decode_resize, getattr(reader, "device_decode_fields", None))
        self._device_shuffle_capacity = int(device_shuffle_capacity or 0)
        #: (field, length) of sequence packing, or None (see the ``pack`` parameter)
        self._pack = _resolve_pack(pack, shuffling_queue_capacity,
                                   self._device_shuffle_capacity, self._pad_shapes,
                                   self.local_batch_size != self.batch_size)
        #: a packing loader takes ragged columns as values and offsets (no
        #: per-row arrays); un-adopted at __exit__
        self._adopted_ragged = False
        if self._pack is not None and hasattr(reader, "keep_ragged"):
            reader.keep_ragged = True
            self._adopted_ragged = True
        #: compressed-page pass-through adoption (ISSUE 14): this loader
        #: finishes the inflate itself (device kernels when a non-CPU backend
        #: is live, the numpy reference otherwise), so the reader must stop
        #: materializing PassthroughColumn values at delivery. Un-adopted at
        #: __exit__ — a reader outliving its loader serves decoded batches
        #: again.
        self._adopted_passthrough = False
        if getattr(reader, "is_batched_reader", False) \
                and hasattr(reader, "keep_passthrough"):
            reader.keep_passthrough = True
            self._adopted_passthrough = True
        #: optional petastorm_tpu.trace.TraceRecorder — per-span chrome-trace view of
        #: the same stages PipelineStats totals (None = zero overhead). The pool
        #: wire joins in: an shm-wire reader records shm.acquire_wait spans too.
        self._trace = trace
        if trace is not None and hasattr(reader, "set_trace"):
            reader.set_trace(trace)
        if getattr(device_transform, "declarative", False):
            if getattr(reader, "ngram", None) is not None:
                # same mismatch the auto-wiring branch below guards: the
                # pipeline's ops name schema fields, but NGram batches are
                # keyed 'offset/field' — it would KeyError inside the jit
                raise ValueError(
                    "a declarative FeaturePipeline cannot be the "
                    "device_transform of an NGram reader: batches are keyed "
                    "'offset/field', not by schema field names. Pass a "
                    "function written against the flat columns instead.")
            # a FeaturePipeline passed directly: compile it against the
            # reader's delivered schema and ride its jittable device function
            # (statistics-dependent ops must have been resolved — device_fn
            # raises with the fix otherwise)
            device_transform = device_transform.device_fn(reader.schema)
        self._device_transform = device_transform
        if device_transform is None:
            spec = getattr(reader, "transform_spec", None)
            if spec is not None and getattr(spec, "device", False) and spec.func is not None:
                if getattr(reader, "ngram", None) is not None:
                    # the spec's func is written against schema field names, but
                    # NGram batches arrive flattened to 'offset/field' columns —
                    # auto-wiring it would KeyError (or silently touch the wrong
                    # columns) on the first batch
                    raise ValueError(
                        "a device TransformSpec cannot be auto-applied to an NGram "
                        "reader: batches are keyed 'offset/field', not by schema "
                        "field names. Pass DataLoader(device_transform=...) written "
                        "against the flat 'offset/field' columns instead.")
                self._device_transform = spec.func
        self._jitted_transform = None
        self._transform_takes_key = False
        self._transform_step = 0
        #: (n) -> frozen (gather index, validity mask) for last_batch='pad'
        self._pad_cache = {}
        self._producer = None
        self._queue = None
        self._dev_queue = None
        self._transfer_thread = None
        self._stop = threading.Event()
        self._producer_error = None
        #: False while a watchdog fail-fast StallError is pending but has not
        #: reached any consumer yet — _start_producer must surface it, not
        #: silently clear it into an empty epoch
        self._producer_error_delivered = True
        #: bumped by every _start_producer(); a superseded iterator's finalizer
        #: compares its captured generation before calling stop() so closing/GC-ing
        #: an old iterator cannot kill the pipeline a newer __iter__ armed
        self._generation = 0
        self.stats = PipelineStats()
        #: host batches the producer has put this iteration, and the number of
        #: the one in transfer: the ``batch=`` of every stage's profiler span
        self._batches_put = self._batch_seq = 0
        #: host batch -> device arrays (the transfer thread's work); its stages
        #: reach the loader through a proxy, so the loader stays free of cycles
        #: and is freed as soon as its last reference goes
        self._transfer = DeviceTransfer(
            self.stats, functools.partial(_Stage, weakref.proxy(self)),
            sharding=sharding, staging=staging, pack=self._pack,
            schema=getattr(reader, "schema", None),
            decode_fields=getattr(reader, "device_decode_fields", None) or (),
            resize=resize,
            seed=seed if seed is not None else getattr(reader, "seed", None))
        # consumer-watermark checkpointing (see state_dict): producer logs
        # (cumulative-delivered-rows, reader state) per delivery; the consumer
        # counts rows actually yielded; state_dict() returns the newest logged
        # state the consumer has fully caught up to. Disabled under shuffling —
        # state_dict() refuses there anyway, and with the device shuffle the
        # consumer count never advances, so the log would grow unpruned forever.
        # Not armed lazily: a state at a past delivery point cannot be
        # reconstructed retroactively, and the throttled snapshots are µs-scale
        # next to each delivery's parquet IO.
        self._ckpt_enabled = (hasattr(reader, "state_dict")
                              and not shuffling_queue_capacity
                              and not self._device_shuffle_capacity
                              and self._pack is None)
        self._ckpt_lock = threading.Lock()
        self._ckpt_log = collections.deque()
        self._ckpt_base = None
        self._rows_consumed = 0
        #: optional petastorm_tpu.obs.health wiring (None = disabled, the
        #: default): heartbeats on every pipeline actor + the stall watchdog +
        #: the flight recorder. Built BEFORE _obs so the metrics wiring can
        #: export the monitor's collector alongside the stage histograms.
        self._health = None
        self._health_owned = False
        self._health_handles = ()
        self._hb_producer = None   # set by the producer thread while it lives
        self._hb_transfer = None   # set by the transfer thread while it lives
        # normalized unconditionally: PTPU_HEALTH=1 must enable monitoring
        # even when health= was not passed (normalize_health handles every
        # shape — None + env, True, HealthOptions, a shared HealthMonitor)
        from petastorm_tpu.obs.health import normalize_health

        self._health, self._health_owned = normalize_health(health)
        self._health_scope = None
        if self._health is not None:
            monitor = self._health
            if self._health_owned:
                # exclusive monitor: bare actor names (loader.producer, ...)
                self._health_scope = monitor
                scope_prefix = None
            else:
                # SHARED monitor: namespace this pipeline's actors so another
                # loader's healthy stamps cannot mask this one's stall (and
                # per-worker latency keys stay per-executor)
                scope_prefix = "pipe%d" % next(_pipeline_seq)
                self._health_scope = monitor.scoped(scope_prefix)
            ref = weakref.ref(self)
            # weak like _LoaderObs: a shared monitor must not pin a dead loader
            self._health_handles = (
                monitor.add_context(
                    "pipeline" if scope_prefix is None
                    else "pipeline/%s" % scope_prefix,
                    lambda: (lambda l: l._health_context() if l is not None
                             else {})(ref())),
                monitor.add_stall_callback(
                    lambda err: (lambda l: l._fail_fast(err) if l is not None
                                 else None)(ref()),
                    prefix=scope_prefix),
            )
            if self._health_owned and metrics:
                # route per-worker latency histograms onto the metrics=
                # registry BEFORE the live executor is rewired below: workers
                # observe latencies the moment set_health lands, and
                # set_registry no-ops once observations exist (re-homing a
                # live family would split it) — wiring it at the _obs block
                # further down raced those first observations onto the
                # default registry
                from petastorm_tpu.obs.metrics import MetricsRegistry, \
                    default_registry

                monitor.set_registry(
                    metrics if isinstance(metrics, MetricsRegistry)
                    else default_registry())
            if hasattr(reader, "set_health"):
                reader.set_health(self._health_scope)
            monitor.start()
        #: optional causal provenance plane (ISSUE 10; None = disabled): one
        #: ProvenanceRecorder collecting per-item spans across every seam —
        #: armed process-globally (worker threads + IO hooks), attached to the
        #: reader (delivery/quarantine notes, pool-child span merge), and fed
        #: batch-plane spans by the producer/transfer/consumer hooks below.
        self._prov_rec = None
        self._prov_owned = False
        #: a recorder the READER factory already attached (provenance= on
        #: make_reader/make_batch_reader, or PTPU_PROVENANCE) is adopted: it
        #: was armed BEFORE the executor started, so it saw every item — a
        #: loader-built recorder attached now can miss items a small plan
        #: already drained through the pool (still fine for long streams)
        from petastorm_tpu.obs import provenance as _prov_mod

        existing = getattr(reader, "_prov", None)
        if isinstance(provenance, _prov_mod.ProvenanceRecorder):
            rec = provenance.arm()  # caller-owned: stays armed past __exit__
        elif existing is not None:
            rec = existing.arm()  # reader-owned: reader.join() disarms
        else:
            # None/True + the PTPU_PROVENANCE env switch, one copy of the
            # policy; a recorder built HERE is this loader's to disarm
            rec = _prov_mod.resolve(provenance)
            self._prov_owned = rec is not None
        if rec is not None:
            if trace is not None:
                rec.set_trace(trace)  # Perfetto flow events into the dump
            rec.set_batch_tracking(not shuffling_queue_capacity
                                   and not self._device_shuffle_capacity
                                   and self._pack is None)
            if hasattr(reader, "set_provenance") and existing is not rec:
                reader.set_provenance(rec)
            self._prov_rec = rec
        #: optional petastorm_tpu.obs wiring (None = disabled, the default):
        #: stage latency histograms + pull collectors for the stats/wire gauges
        self._obs = None
        if metrics:
            from petastorm_tpu.obs.metrics import MetricsRegistry, default_registry

            registry = metrics if isinstance(metrics, MetricsRegistry) \
                else default_registry()
            if self._health is not None and self._health_owned:
                # a loader-owned monitor exports its per-worker latency
                # histograms beside the stage histograms (a SHARED monitor
                # keeps whatever registry its owner configured)
                self._health.set_registry(registry)
            self._obs = _LoaderObs(registry, self)
        #: optional SLO/anomaly engine (ISSUE 12) over the registry's windowed
        #: time-series: attached to the timeline store's sampling cadence, so
        #: the loader hot paths never see it. Breach alerts carry an
        #: attribution snapshot when provenance is on.
        self._slo_engine = None
        self._slo_owned = False
        if slos:
            if self._obs is None:
                raise ValueError(
                    "DataLoader(slos=...) requires metrics= — the SLO engine "
                    "evaluates the metrics registry's windowed time-series")
            from petastorm_tpu.obs.slo import SloEngine

            registry = self._obs.registry
            if isinstance(slos, SloEngine):
                # caller-supplied (shared) engine: like a shared
                # HealthMonitor/ProvenanceRecorder, its lifecycle stays the
                # caller's — never detached at __exit__, and never re-homed
                # off a store the caller already attached it to
                engine = slos
                if engine._registry is None:
                    engine._registry = registry
                if engine._store is None:
                    engine.attach(registry.timeline_store())
            else:
                engine = SloEngine(specs=list(slos), registry=registry)
                engine.attach(registry.timeline_store())
                self._slo_owned = True
            if engine._attribution is None and self._prov_rec is not None:
                engine.set_attribution(self.attribution_report)
            self._slo_engine = engine
        #: optional closed-loop controller (ISSUE 13; requires ``metrics=``):
        #: rides the same window cadence as the SLO engine and actuates the
        #: reader's live knobs (readahead depth/bytes, GET pool width, hedge
        #: quantile, mem-tier budget, worker fleet) through the sanctioned
        #: KnobSet seam. ``True`` = default rules over the standard knobs; a
        #: ControlOptions tunes warmup/cooldown/no-gain policy; a pre-built
        #: Controller is shared (caller-owned lifecycle). With provenance on,
        #: rules read the attribution snapshot (culprit-site triggers).
        self._controller = None
        self._ctl_owned = False
        if controller:
            if self._obs is None:
                raise ValueError(
                    "DataLoader(controller=...) requires metrics= — the "
                    "controller reads the registry's windowed time-series")
            from petastorm_tpu.control import (ControlOptions, Controller,
                                               build_knobset)

            registry = self._obs.registry
            if isinstance(controller, Controller):
                # caller-supplied (shared): lifecycle stays the caller's —
                # never detached at __exit__ (same convention as slos=)
                ctl = controller
                if ctl._registry is None:
                    ctl._registry = registry
                if ctl._store is None:
                    ctl.attach(registry.timeline_store())
            else:
                ctl_opts = controller \
                    if isinstance(controller, ControlOptions) else None
                ctl = Controller(build_knobset(reader), registry=registry,
                                 options=ctl_opts)
                ctl.attach(registry.timeline_store())
                self._ctl_owned = True
            if ctl._attribution is None and self._prov_rec is not None:
                ctl.set_attribution(self.attribution_report)
            self._controller = ctl
            self._obs.add_collector("ctl", ctl.collect)

    # -- producer (background thread: reader → host batches) ---------------------------
    #
    # The host-batch queue is passed IN (not read off self) so a thread from a
    # superseded iteration that outlives join()'s timeout keeps draining/feeding its
    # OWN queue and can never steal batches from the queue a newer __iter__ installed.

    def _ckpt_record(self, cum_rows):
        """Producer side of consumer-watermark checkpointing: log the reader's state
        as of ``cum_rows`` delivered rows, pruning entries the consumer already
        passed (keeps the log ~in-flight-sized even over infinite epochs)."""
        state = self.reader.state_dict()
        with self._ckpt_lock:
            log = self._ckpt_log
            c = self._rows_consumed
            while len(log) >= 2 and log[1][0] <= c:
                log.popleft()
            if log and log[0][0] <= c:
                self._ckpt_base = log.popleft()[1]
            log.append((cum_rows, state))

    def _produce(self, q):
        batcher = _HostBatcher(self.local_batch_size, self._shuffling_queue_capacity,
                               self._seed)
        packer = None
        if self._pack is not None:
            from petastorm_tpu.ops.packing import Packer

            packer = Packer(self.local_batch_size, self._pack[1])
        stats = self.stats
        # health wiring (ISSUE 5): one heartbeat for this producer thread,
        # stamped at the stage sites (disabled = hb is None, one check per
        # site); _Stage gives the flight ring its span edges. Registration
        # goes through the SCOPE (namespaced on shared monitors).
        scope = self._health_scope
        hb = None
        flight = None
        if scope is not None:
            hb = scope.register("loader.producer", "producer")
            flight = scope.flight
        self._hb_producer = hb
        ckpt_cum = 0  # cumulative rows delivered by the reader this generation
        ckpt_deliveries = 0
        ckpt_next_snap = 1
        tally_seen = tallies()  # stage-1 totals fold in per delivery
        # shm wire integration: gauges refresh per delivery
        wire_stats_fn = getattr(self.reader, "wire_stats", None)
        if wire_stats_fn is not None and not wire_stats_fn():
            wire_stats_fn = None  # thread/dummy pool or socket wire: nothing to poll
        wire_views = bool(getattr(self.reader, "wire_views", False))
        # Lease retention (ISSUE 6): on the plain batched path the view-mode
        # delivery's lease is TAKEN from the reader and rides the batcher's
        # chunk deque and every batch cut from it — the old per-delivery
        # copy-out disappears. Shuffling buffers (rows linger indefinitely),
        # per-row readers (rows are restacked anyway), and staged device-decode
        # payloads (opaque objects the batcher cannot track) still detach,
        # charged to the ``loader_detach`` census site.
        take_lease_fn = getattr(self.reader, "take_lease", None)
        lease_mode = (wire_views and take_lease_fn is not None
                      and not self._shuffling_queue_capacity
                      and bool(getattr(self.reader, "is_batched_reader", False))
                      and not getattr(self.reader, "device_decode_fields", None))
        detach_views = wire_views and not lease_mode
        try:
            it = iter(self.reader)
            while True:
                if hb is not None:
                    hb.beat("read")
                with _Stage(self, "reader.next", batch=self._batches_put):
                    item = next(it, _SENTINEL)
                if item is _SENTINEL:
                    # final snapshot: the all-delivered state must be reachable
                    # even when the throttle skipped the tail deliveries
                    if self._ckpt_enabled and ckpt_deliveries:
                        self._ckpt_record(ckpt_cum)
                    if wire_stats_fn is not None:
                        stats.update_wire(wire_stats_fn())
                    stats.fold_tallies(tally_seen)
                    break
                if self._stop.is_set():
                    return
                # batched readers yield columnar dicts; per-row readers yield one row per
                # item (branching on the reader contract, not a shape heuristic — a row
                # whose fields are all equal-length ndarrays must NOT be read as a batch)
                if getattr(self.reader, "is_batched_reader", False):
                    columns = item._asdict() if hasattr(item, "_asdict") else item
                    if not isinstance(columns, dict):
                        raise TypeError("unexpected reader item %r" % type(item))
                    columns = {k: v for k, v in columns.items() if v is not None}
                else:
                    if getattr(self.reader, "ngram", None) is not None:
                        # NGram windows arrive as {offset: row}: flatten to
                        # 'offset/field' columns so every timestep's tensors reach
                        # the device as ordinary static-shape arrays (shardings,
                        # pad_shapes, and transforms key by the flat name)
                        item = _flatten_ngram_window(item)
                    columns = _rows_to_columns(
                        [item],
                        object_fields=getattr(self.reader, "device_decode_fields", ()),
                    )
                lease = take_lease_fn() if lease_mode else None
                if detach_views:
                    columns = detach_slab_views(columns)
                if wire_stats_fn is not None:
                    stats.update_wire(wire_stats_fn())
                stats.fold_tallies(tally_seen)
                if packer is not None:
                    try:  # the packer copies the tokens out of the delivery
                        if not self._pack_delivery(q, packer, columns, hb):
                            return
                    finally:
                        if lease is not None:
                            lease.release()
                    continue
                if hb is not None:
                    hb.beat("batch")
                with _Stage(self, "batch.form", batch=self._batches_put) as st:
                    if self._pad_shapes:
                        columns = _pad_ragged_columns(columns, self._pad_shapes)
                    if self._shuffling_queue_capacity:
                        # the shuffling buffer permutes ROWS — compressed pages
                        # cannot be row-permuted without decoding, so this seam
                        # inflates on host (counted; pagedec=auto never pairs
                        # with a host shuffle on purpose — the HBM ring shuffle
                        # is the pass-through-compatible one)
                        columns = _materialize_passthrough(
                            columns, cause="pagedec_host_inflate")
                        # rows linger in the shuffling buffer across row groups: staged
                        # payloads that are views into a row group's stacked buffers must be
                        # detached or one straggler row pins its whole group's memory
                        for name in getattr(self.reader, "device_decode_fields", ()):
                            col = columns.get(name)
                            if col is not None and col.dtype == object:
                                for i, v in enumerate(col):
                                    if hasattr(v, "detach"):
                                        col[i] = v.detach()
                    ready = batcher.add(columns, lease)
                collate_span = (st.t0, st.dt)
                if self._ckpt_enabled:
                    ckpt_cum += _batch_row_count(columns)
                    # Snapshot at delivery boundaries (batched reader items ≈ row
                    # groups; per-row readers at batch cuts), geometrically
                    # throttled: Reader.state_dict() rebuilds the consumed map
                    # (O(groups log groups)), so per-delivery snapshots would make
                    # the producer O(n²) over a long epoch (review r5). After 512
                    # unthrottled snapshots the stride grows with the delivery
                    # count — ~512 more per epoch, bounding restore replay to
                    # ~deliveries/512 extra row groups while keeping small
                    # datasets exact.
                    ckpt_deliveries += 1
                    if (ready or getattr(self.reader, "is_batched_reader", False)) \
                            and ckpt_deliveries >= ckpt_next_snap:
                        self._ckpt_record(ckpt_cum)
                        ckpt_next_snap = ckpt_deliveries \
                            + max(1, ckpt_deliveries // 512)
                if not self._deliver_batches(q, ready, hb,
                                             collate_span=collate_span):
                    return
            # tail flush: the same per-batch stop check as the main loop — a stop()
            # during the flush must not leave the producer blocked on an untimed put
            # after the consumer already exited on the re-injected sentinel. Under
            # last_batch='drop' the shuffling buffer can still hold whole batches at
            # reader exhaustion — only the short tail is dropped.
            if packer is not None:
                with _Stage(self, "pack", batch=self._batches_put) as st:
                    tail = self._packed_batch(packer.finish(), st)
                if tail is not None:
                    self._deliver_batches(q, [tail], hb)
                return
            if not self._deliver_batches(q, batcher.finish(), hb,
                                         drop_short=self.last_batch == "drop"):
                return
        except Exception as e:  # noqa: BLE001 — surfaced to consumer thread
            self._producer_error = e
            if flight is not None:
                flight.record("producer_error", error=repr(e))
        finally:
            # drop the batcher's holds on chunks that never formed a batch
            # (teardown mid-epoch): their slabs go back to the ring now
            batcher.close()
            if flight is not None:
                flight.record("queue", event="producer_end_of_stream")
            if hb is not None:
                hb.done()
            self._hb_producer = None
            _put_sentinel(q, self._stop)

    def _pack_delivery(self, q, packer, columns, hb):
        """Pack one reader delivery: feed its documents, then hand on every batch
        they complete, each under a ``pack`` stage of its own (the last stage of a
        delivery places the chunks that start the next batch). Returns False once
        the loader is stopped."""
        field = self._pack[0]
        if set(columns) != {field}:
            raise ValueError(
                "pack= packs the field %r alone, but the reader delivers %s: open it "
                "with schema_fields=[%r]" % (field, sorted(columns), field))
        docs = columns[field]
        while True:
            if hb is not None:
                hb.beat("batch")
            with _Stage(self, "pack", batch=self._batches_put) as st:
                if docs is not None:
                    packer.feed(docs)
                    docs = None
                batch = self._packed_batch(packer.next_batch(), st)
            if batch is None:
                return True
            if not self._deliver_batches(q, [batch], hb):
                return False

    def _packed_batch(self, packed, st):
        """A :class:`~petastorm_tpu.ops.packing.PackedBatch` as a host batch: the
        tokens, the chunk table under ``_PACK_TABLE`` and, under ``last_batch=
        'pad'``, ``__valid__``. A batch with empty rows (the part-filled batch at
        the end of the stream) follows ``last_batch``; None for none. Its counts
        go to the stats and to the open ``pack`` span."""
        if packed is None:
            return None
        tokens = packed.tokens
        if packed.rows_used < len(tokens):
            if self.last_batch == "drop":
                return None
            if self.last_batch == "partial":
                tokens = tokens[:packed.rows_used]
        pad_slots = tokens.size - (packed.tokens.size - packed.pad_slots)
        st.set_metadata(chunks=packed.chunks, split_docs=packed.split_docs,
                        pad_slots=pad_slots)
        stats = self.stats
        stats.pack_slots += tokens.size
        stats.pack_pad_slots += pad_slots
        stats.pack_chunks += packed.chunks
        batch = {self._pack[0]: tokens, _PACK_TABLE: packed.table}
        if self.last_batch == "pad":
            batch["__valid__"] = np.arange(len(tokens)) < packed.rows_used
        return batch

    def _put_batch(self, q, batch, hb=None, bp=None):
        """Producer put into the host queue, timed: blocking here is DOWNSTREAM
        backpressure (decode/transfer/step slower than the producer) — the
        bottleneck analyzer's consumer-bound signal (``put_wait_s``) and, for
        the stall watchdog, a ``wait:`` state that must NEVER read as a stall
        (a full queue means the consumer is the slow one, not this thread)."""
        if hb is not None:
            hb.wait("host_queue_put")
        with _Stage(self, "wait.host_queue_put", bp, batch=self._batches_put):
            ok = _put_with_stop(q, batch, self._stop)
        self._batches_put += ok
        if hb is not None:
            hb.beat("batch")
        return ok

    def _deliver_batches(self, q, batches, hb, drop_short=False,
                         collate_span=None):
        """Push cut batches into the host queue, padding per ``last_batch``.
        Returns False once the loader is stopped (or the put gives up); on any
        early exit — and for a ``drop_short`` tail — the undelivered batches'
        leases are released so teardown never strands a slab hold until GC.

        Provenance (ISSUE 10): each batch opens its BatchProvenance here —
        membership attributed from the delivery FIFO, the collate span split
        across the cut set — and a batch that dies on this path is retired so
        the transfer/delivery pointers stay aligned."""
        rec = self._prov_rec
        collate_t0 = collate_share = None
        if rec is not None and collate_span is not None and batches:
            collate_t0 = collate_span[0]
            collate_share = collate_span[1] / len(batches)
        for i, batch in enumerate(batches):
            bp = None
            if rec is not None:
                bp = rec.producer_cut(_batch_row_count(batch), collate_t0,
                                      collate_share)
            if self._stop.is_set():
                if bp is not None:
                    rec.batch_dropped(bp)
                for b in batches[i:]:
                    _release_leases(b)
                return False
            if drop_short and _batch_row_count(batch) < self.local_batch_size:
                if bp is not None:
                    rec.batch_dropped(bp)
                _release_leases(batch)
                continue
            if self.last_batch == "pad":
                batch = self._pad(batch)
            if not self._put_batch(q, batch, hb, bp):
                if bp is not None:
                    rec.batch_dropped(bp)
                _release_leases(batch)
                for b in batches[i + 1:]:
                    _release_leases(b)
                return False
        return True

    def _pad(self, batch):
        n = len(next(iter(batch.values()))) if batch else 0
        if n == 0 or n == self.local_batch_size:
            if batch and "__valid__" not in batch:
                batch["__valid__"] = np.ones(n, dtype=bool)
            return batch
        pad = self.local_batch_size - n
        # pass-through columns inflate on host before the gather below (a
        # short TAIL batch only — full batches never reach this line)
        batch = _materialize_passthrough(batch, cause="pagedec_host_inflate")
        # the gather index and validity mask depend only on (n, batch_size):
        # built once per row count and frozen, instead of the old
        # np.concatenate([arange, full]) rebuild on every partial batch
        cached = self._pad_cache.get(n)
        if cached is None:
            idx = np.concatenate([np.arange(n), np.full(pad, n - 1)])
            idx.flags.writeable = False
            valid = np.concatenate([np.ones(n, dtype=bool),
                                    np.zeros(pad, dtype=bool)])
            valid.flags.writeable = False
            cached = self._pad_cache[n] = (idx, valid)
        idx, valid = cached
        leases = take_leases(batch)
        out = {}
        copied = 0
        for name, arr in batch.items():
            if isinstance(arr, np.ndarray):
                gathered = arr[idx]  # fancy indexing: an owned copy...
                if arr.dtype == object:
                    # ...of the OUTER pointers only: ragged ELEMENTS may still
                    # be read-only views into a leased slab the release below
                    # recycles — copy them owned (what detach_slab_views does
                    # on the non-lease path)
                    for i, e in np.ndenumerate(gathered):
                        if isinstance(e, np.ndarray) and not e.flags.writeable:
                            gathered[i] = e.copy()
                            copied += e.nbytes
                else:
                    copied += gathered.nbytes
                out[name] = gathered
            else:  # non-ndarray sequence: repeat the last element so every column is
                out[name] = list(arr) + [arr[-1]] * pad  # batch_size long (ADVICE r1)
        count_copy("loader_pad", copied)
        for lease in leases:
            lease.release()  # every column was gathered out of the leased views
        out["__valid__"] = valid.copy()  # consumers own (and may mutate) the mask
        return out

    # -- consumer side ------------------------------------------------------------------

    def _advance_consumed(self, n):
        """Bump the consumer watermark under the checkpoint lock: the producer
        prunes the delivery log against ``_rows_consumed`` concurrently
        (``_ckpt_record``), so an unlocked ``+=`` could tear against that read."""
        if n:
            with self._ckpt_lock:
                self._rows_consumed += n

    def _start_producer(self):
        """Arm the pipeline for a fresh iteration. MUST run on the consumer thread
        (ADVICE r2: ``_stop.clear()`` used to run on the transfer thread at first
        advance, so a ``stop()`` issued around iteration start could be silently
        undone, and a second ``__iter__`` could race a still-live previous set of
        threads). A new ``__iter__`` supersedes any previous one: the old pipeline
        is stopped and joined before state is reset."""
        if (self._producer is not None and self._producer.is_alive()) or (
                self._transfer_thread is not None and self._transfer_thread.is_alive()):
            self.stop()
            self.join()
            if (self._producer is not None and self._producer.is_alive()) or (
                    self._transfer_thread is not None
                    and self._transfer_thread.is_alive()):
                # join() timed out: resetting _stop under a live thread would let a
                # zombie keep running into the new iteration — refuse instead
                raise RuntimeError(
                    "previous DataLoader iteration did not shut down within the join "
                    "timeout (a pipeline thread is still alive — likely stuck in a "
                    "long device dispatch); cannot safely start a new iteration")
        self._generation += 1
        self._stop.clear()
        pending = self._producer_error
        if pending is not None and not self._producer_error_delivered:
            from petastorm_tpu.errors import StallError

            if isinstance(pending, StallError):
                # the watchdog fail-fasted while no consumer was iterating
                # (pre-iteration or between epochs): the reader is already
                # stopped/truncated, and the debounced watchdog will not
                # re-report the same hang — clearing here would turn a
                # detected stall into a silently empty epoch
                self._producer_error_delivered = True
                raise pending
        self._producer_error = None
        self._producer_error_delivered = True
        self._batches_put = 0
        self.stats.reset()
        if self._obs is not None:
            # percentiles re-anchor with the totals: bottleneck_report() must
            # describe ONE window, never fresh totals + stale histograms
            self._obs.reset_stage_histograms()
        if self._ckpt_enabled:
            with self._ckpt_lock:
                # fresh watermark per iteration: base = reader state BEFORE any of
                # this generation's deliveries (a restore target of "nothing from
                # this iteration consumed yet")
                self._ckpt_log.clear()
                self._ckpt_base = self.reader.state_dict()
                self._rows_consumed = 0
        self._queue = queue.Queue(maxsize=max(2, self._host_queue_size))
        self._dev_queue = None
        self._producer = threading.Thread(target=self._produce, args=(self._queue,),
                                          name="ptpu-loader", daemon=True)
        self._producer.start()

    def _host_batches(self, q):
        stats = self.stats
        for seq in itertools.count():  # host batches leave the queue in put order
            hb = self._hb_transfer  # live only while the transfer thread runs
            if hb is not None:
                hb.wait("host_queue")  # starvation = upstream's problem
            with _Stage(self, "wait.host_queue", batch=seq):
                item = q.get()
            if item is _SENTINEL:
                if self._producer_error is not None:
                    self._producer_error_delivered = True
                    raise self._producer_error
                return
            stats.batches += 1
            stats.rows += len(next(iter(item.values()))) if item else 0
            self._batch_seq = seq
            yield item

    def _transfer_next(self, batch):
        """The next host batch through the transfer stage: ``(device arrays,
        host-only columns)``."""
        if self._prov_rec is not None:
            # host batches flow to this thread strictly FIFO: advance the
            # recorder's transfer pointer to this batch's provenance
            self._prov_rec.transfer_next()
        hb = self._hb_transfer
        return self._transfer(batch, self._batch_seq,
                              None if hb is None else hb.beat)

    def _to_device(self, batch):
        arrays, host = self._transfer_next(batch)
        arrays = self._apply_device_transform(arrays)
        arrays.update(host)
        return arrays

    def _apply_device_transform(self, arrays):
        if self._device_transform is None:
            return arrays
        import jax

        if self._jitted_transform is None:
            import inspect

            try:
                n_params = len(inspect.signature(
                    self._device_transform).parameters)
            except (TypeError, ValueError):
                n_params = 1
            self._transform_takes_key = n_params >= 2
            self._jitted_transform = jax.jit(self._device_transform)
        if self._transform_takes_key:
            key = jax.random.fold_in(
                jax.random.PRNGKey(self._seed or 0), self._transform_step)
            self._transform_step += 1
            return self._jitted_transform(arrays, key)
        return self._jitted_transform(arrays)

    def _device_batches(self, host_q):
        """host batches → ``(device batch, local_rows)``, with the optional HBM
        exchange shuffle between transfer and transform (rows are decorrelated over
        a ~capacity window by one fused gather+scatter per batch — zero host work).

        ``local_rows`` is the HOST batch's row count — the unit the checkpoint
        watermark needs: under multi-process JAX the assembled device batch has the
        GLOBAL leading dim, but the producer's delivery log counts this process's
        reader rows, and mixing the two would advance the watermark process_count×
        too fast (skipping buffered rows on restore)."""
        if not self._device_shuffle_capacity:
            for batch in self._host_batches(host_q):
                if self._stop.is_set():
                    _release_leases(batch)
                    return
                n = _batch_valid_rows(batch)
                yield self._to_device(batch), n
            return
        from petastorm_tpu.ops.device_shuffle import DeviceShuffleBuffer

        # the ring is laid out like the batches (capacity axis where the batch
        # axis is), so the resident rows split across devices, not replicate
        shuffler = DeviceShuffleBuffer(
            self._device_shuffle_capacity, seed=self._seed or 0,
            shardings=functools.partial(column_sharding, self.sharding))
        for batch in self._host_batches(host_q):
            if self._stop.is_set():
                _release_leases(batch)
                return
            arrays, host = self._transfer_next(batch)
            if host:
                raise ValueError(
                    "device_shuffle_capacity requires every delivered column to be "
                    "device-resident, but %s are host-only (strings/objects cannot "
                    "live in the HBM ring). Narrow schema_fields or drop the device "
                    "shuffle." % sorted(host)
                )
            out = shuffler.push(arrays)
            if out is not None:
                # local_rows 0: shuffled rows have no watermark (state_dict refuses
                # under device shuffle), so the count is never consulted
                yield self._apply_device_transform(out), 0
        for out in shuffler.drain():
            if self._stop.is_set():
                return
            yield self._apply_device_transform(out), 0

    def __iter__(self):
        self._start_producer()
        gen = self._generation  # superseded iterators must not stop a newer pipeline
        host_q = self._queue
        if not self.to_device:
            # staged decode still has to finish (decode runs on device, delivery is
            # host numpy) so CPU-only consumers see images, not coefficient payloads
            if getattr(self.reader, "device_decode_fields", None):
                for batch in self._host_batches(host_q):
                    # host delivery IS the designed host-decode seam for
                    # pass-through columns (no degradation counted)
                    batch = _materialize_passthrough(batch)
                    rest, staged = self._transfer.decode(batch)
                    rest.update({k: np.asarray(v) for k, v in staged.items()})
                    self._advance_consumed(_batch_valid_rows(rest))
                    if self._prov_rec is not None:
                        self._prov_rec.batch_delivered()
                    yield rest
            else:
                # lease-backed batches stay valid until the consumer asks for
                # the NEXT one (same cadence as Reader.release_batch): the
                # previous batch's slabs return to the ring here, and the last
                # one's at generator close
                prev = None
                try:
                    for batch in self._host_batches(host_q):
                        if prev is not None:
                            prev.release()
                        prev = batch if isinstance(batch, LeasedBatch) else None
                        batch = _materialize_passthrough(batch)
                        if self._pack is not None:
                            batch = self._transfer.layout_host(batch)
                        self._advance_consumed(_batch_valid_rows(batch))
                        if self._prov_rec is not None:
                            self._prov_rec.batch_delivered()
                        yield batch
                finally:
                    if prev is not None:
                        prev.release()
            return
        if self.prefetch <= 0:  # synchronous transfer (debug)
            for batch, local_rows in self._device_batches(host_q):
                self._advance_consumed(local_rows)
                if self._prov_rec is not None:
                    self._prov_rec.batch_delivered()
                yield batch
            return
        # Async transfer thread: host batches → the transfer stage → a small
        # device-batch queue. Keeping dispatch OFF the consumer thread both overlaps
        # H2D/decode with the training step and absorbs device-service latency spikes
        # (a slow dispatch drains the queue instead of stalling the step).
        dev_q = queue.Queue(maxsize=max(1, self.prefetch))
        self._dev_queue = dev_q
        transfer_error = []

        def _transfer_loop():
            scope = self._health_scope
            hb = None
            if scope is not None:
                hb = scope.register("loader.transfer", "transfer")
                self._hb_transfer = hb
            try:
                for batch_rows in self._device_batches(host_q):
                    if self._stop.is_set():
                        return
                    if hb is not None:
                        # a full device queue means the TRAINING STEP is the
                        # slow one — a wait, never a stall
                        hb.wait("device_queue_put")
                    if not _put_with_stop(dev_q, batch_rows, self._stop):
                        return
            except Exception as e:  # noqa: BLE001 — surfaced to consumer thread
                transfer_error.append(e)
            finally:
                if hb is not None:
                    hb.done()
                self._hb_transfer = None
                _put_sentinel(dev_q, self._stop)

        self._transfer_thread = threading.Thread(
            target=_transfer_loop, name="ptpu-transfer", daemon=True)
        self._transfer_thread.start()
        finished = False
        try:
            for seq in itertools.count():  # device batches arrive in host-batch order
                # bounded wait (GL-R001): the transfer thread's finally puts a
                # sentinel on every exit path, but a thread that died without
                # one (killed hard mid-put, interpreter teardown race) used to
                # hang this consumer forever — re-check liveness each second
                # and surface the stored error / end the epoch instead
                with _Stage(self, "wait.device_queue", batch=seq):
                    while True:
                        try:
                            item = dev_q.get(timeout=1.0)
                            break
                        except queue.Empty:
                            t_thread = self._transfer_thread
                            if t_thread is None or not t_thread.is_alive():
                                item = _SENTINEL
                                break
                if item is _SENTINEL:
                    finished = True
                    if transfer_error:
                        raise transfer_error[0]
                    if self._producer_error is not None:
                        # normally the transfer thread re-raises the producer's
                        # error through _host_batches, but a watchdog fail-fast
                        # (StallError) injects the sentinel DIRECTLY into this
                        # queue — the error must still surface, not silently
                        # end the epoch
                        self._producer_error_delivered = True
                        raise self._producer_error
                    return
                batch, local_rows = item
                self._advance_consumed(local_rows)
                if self._prov_rec is not None:
                    self._prov_rec.batch_delivered()
                yield batch
        finally:
            if not finished and gen == self._generation:
                # iterator abandoned mid-epoch (break / del): stop the pipeline so the
                # transfer thread does not keep pinning prefetched device batches.
                # Guarded by generation: closing a SUPERSEDED iterator (a newer
                # __iter__ already re-armed the loader) must not kill the new one.
                self.stop()

    # -- lifecycle ----------------------------------------------------------------------

    def _fail_fast(self, err):
        """Stall-watchdog escalation (``escalation="raise"``): surface ``err``
        to the consumer and unwedge every queue — the training loop gets a
        :class:`~petastorm_tpu.errors.StallError` instead of hanging. The
        reader is stopped too (truncation semantics, same as a user ``stop()``)
        so a producer blocked inside ``reader.next`` wakes promptly; a worker
        thread wedged in native code stays behind as a daemon and is reported
        by the executor's ``thread_join_timeout`` degradation at join."""
        self._producer_error = err
        self._producer_error_delivered = False
        try:
            self.reader.stop()
        except Exception:  # noqa: BLE001 — fail-fast must not die on teardown
            pass  # graftlint: disable=GL-O002 (the StallError itself is the signal)
        self.stop()

    def _health_context(self):
        """Queue depths + stats + io gauges, snapshotted into flight records
        (the watchdog's evidence of WHERE the pipeline was backed up)."""
        q = self._queue
        dq = self._dev_queue
        out = {
            "host_queue_depth": q.qsize() if q is not None else 0,
            "host_queue_size": max(2, self._host_queue_size),
            "device_queue_depth": dq.qsize() if dq is not None else 0,
            "device_queue_size": max(1, self.prefetch),
            "stats": self.stats.snapshot(),
        }
        for name in ("io_stats", "wire_stats"):
            fn = getattr(self.reader, name, None)
            if fn is not None:
                try:
                    polled = fn()
                except Exception:  # noqa: BLE001 — evidence is best-effort
                    polled = None
                if polled:
                    out[name.replace("_stats", "")] = polled
        rec = self._prov_rec
        if rec is not None:
            # attribution summary rides into the flight record on stall: the
            # operator sees WHICH site owned the critical path when it hung
            try:
                out["attribution"] = rec.summary()
            except Exception:  # noqa: BLE001 — evidence is best-effort
                out["attribution"] = None
        if self._slo_engine is not None:
            # temporal plane (ISSUE 12): recent SLO alerts into the flight
            # context — a stall that followed a burn shows the burn
            alerts = self._slo_engine.alerts()
            out["slo"] = {
                "alerts": len(alerts),
                "breaching": self._slo_engine.breaching(),
                "last_alert": alerts[-1].message if alerts else None,
            }
        return out

    def health_report(self, dump_path=None):
        """On-demand health snapshot (requires the loader to have been built
        with ``health=``): the full flight-record dict — heartbeat ages and
        states, driver (and pool-child) stacks, queue depths, degradation
        counts, per-worker latency, the recent-event ring — plus the
        bottleneck analyzer's verdict under ``"bottleneck"``. Pass
        ``dump_path`` to also write it as a JSON flight record."""
        if self._health is None:
            raise ValueError(
                "DataLoader was built without health monitoring — pass "
                "health=True (or a HealthOptions/HealthMonitor, or set "
                "PTPU_HEALTH=1) to enable health_report()")
        report = self._health.capture("on_demand")
        report["bottleneck"] = self.bottleneck_report().to_dict()
        if dump_path is not None:
            from petastorm_tpu.obs.flight import write_flight_record

            write_flight_record(dump_path, report)
        return report

    def stop(self):
        self._stop.set()
        for q in (self._queue, self._dev_queue):
            if q is not None:
                # unblock a producer/transfer thread stuck on a full queue. Catches
                # Exception rather than queue.Empty: stop() can run from a generator
                # finalizer during interpreter shutdown, when the queue module's
                # globals (incl. Empty) may already be torn down to None.
                try:
                    while True:
                        item = q.get_nowait()
                        # a drained batch may still carry slab/staging leases —
                        # return them to their rings now instead of stranding
                        # them until GC (counted as ptpu_lease_leaked_total)
                        try:
                            _release_leases(item)
                        except Exception:  # noqa: BLE001
                            pass  # graftlint: disable=GL-O002 (teardown: lease module may be torn down)
                except Exception:  # noqa: BLE001
                    pass  # graftlint: disable=GL-O002 (interpreter teardown: queue globals may be None)
                # the drain may have consumed the producer's end-of-stream sentinel
                # while the downstream thread is blocked in an untimed get() with the
                # producer already exited (ADVICE r2 teardown race) — re-put it so the
                # blocked get always wakes. The queue was just drained, so put_nowait
                # cannot be full except under a concurrent producer put, in which case
                # that put itself unblocks the get.
                try:
                    q.put_nowait(_SENTINEL)
                except Exception:  # noqa: BLE001
                    pass  # graftlint: disable=GL-O002 (interpreter teardown: queue globals may be None)
        # host-wide cache arena (ISSUE 17): the consumer is going away — sweep
        # holder refcounts left by processes that died without releasing (a
        # SIGKILLed pool child mid-read), so their pinned entries become
        # evictable again. Live peers' views are untouched; same exit-drain
        # discipline as the lease release above.
        try:
            from petastorm_tpu.io import arena as _arena_mod

            arena_obj = _arena_mod.process_arena()
            if arena_obj is not None:
                arena_obj.reclaim()
        except Exception:  # noqa: BLE001
            pass  # graftlint: disable=GL-O002 (interpreter teardown: arena module may be torn down)

    def join(self):
        if self._producer is not None:
            self._producer.join(timeout=60)
        if self._transfer_thread is not None:
            self._transfer_thread.join(timeout=60)

    # -- consumer-watermark checkpointing ----------------------------------------------

    @property
    def cur_shard(self):
        """This process's shard id (the reader's), so a ``DataLoader`` duck-types as
        a checkpointable reader for :mod:`petastorm_tpu.checkpoint` routing."""
        return getattr(self.reader, "cur_shard", None)

    def state_dict(self):
        """Exact-resume state at the CONSUMER watermark — checkpoint through the
        loader, not the reader, when batches flow through a ``DataLoader``.

        ``Reader.state_dict()`` marks a row group consumed when the reader hands it
        to whoever calls ``next()`` — here the loader's background producer, which
        runs ahead of the training loop by the prefetch/host-queue depth. Saving
        the READER's state mid-stream would therefore skip every row sitting in
        the loader's buffers on restore (delivered, never trained on). This method
        instead returns the newest reader state whose deliveries the consumer has
        FULLY received — rows in flight inside the loader replay after restore
        (the same at-least-once row-group granularity ``Reader.state_dict``
        documents), and nothing is lost.

        Works with any :mod:`petastorm_tpu.checkpoint` entry point (the loader
        duck-types as a reader): ``ptck.save(path, loader)``,
        ``ocp.args.Composite(reader=ptck.save_args(loader))``, pod-exact included.

        Raises for shuffling loaders (host ``shuffling_queue_capacity`` or
        ``device_shuffle_capacity``): a row can linger in a random-exchange buffer
        arbitrarily long, so no row-group watermark short of the epoch boundary is
        correct — checkpoint those at epoch ends via ``Reader.state_dict()``.
        """
        if self._pack is not None:
            raise ValueError(
                "DataLoader.state_dict() is not available with pack=: the batch "
                "being packed holds chunks of documents from deliveries the "
                "reader already counts as consumed, and that carried state is not "
                "checkpointed yet. Checkpoint at an epoch boundary with "
                "Reader.state_dict() instead.")
        if self._shuffling_queue_capacity or self._device_shuffle_capacity:
            raise ValueError(
                "DataLoader.state_dict() is not available with shuffling enabled "
                "(shuffling_queue_capacity/device_shuffle_capacity): shuffled rows "
                "linger in the buffer indefinitely, so a mid-epoch row-group "
                "watermark would lose them. Checkpoint at an epoch boundary with "
                "Reader.state_dict() instead.")
        if not self._ckpt_enabled:
            raise AttributeError(
                "underlying reader %r has no state_dict" % type(self.reader).__name__)
        with self._ckpt_lock:
            state = self._ckpt_base
            for cum, st in self._ckpt_log:
                if cum <= self._rows_consumed:
                    state = st
                else:
                    break
        if state is None:  # never iterated: the reader's current state IS the truth
            state = self.reader.state_dict()
        return state

    def load_state_dict(self, state):
        """Restore into the underlying reader (before iterating)."""
        self.reader.load_state_dict(state)

    @property
    def quarantine_report(self):
        """The underlying reader's poison-item
        :class:`~petastorm_tpu.recovery.QuarantineReport` (ISSUE 7): every plan
        item skipped under ``RecoveryOptions(on_poison="quarantine")`` with its
        plan ordinals, file/row-group identity, and exception chain. Falsy when
        nothing was quarantined; ``None`` for readers without the recovery
        machinery (e.g. an ``InMemDataset`` source)."""
        return getattr(self.reader, "quarantine_report", None)

    def bottleneck_report(self):
        """Name the limiting pipeline stage from the stage counters: a
        :class:`petastorm_tpu.obs.analyze.BottleneckReport` with verdict
        ``producer-bound`` / ``wire-bound`` / ``consumer-bound`` / ``balanced``
        and per-side utilization fractions (``print(report)`` for the
        human-readable rendering; p50/p90/p99 stage detail attached when the
        loader was built with ``metrics=``). Reads the CURRENT ``stats``
        window — call after (or during) iteration. With ``provenance=``,
        :meth:`attribution_report` refines this down to a concrete SITE."""
        from petastorm_tpu.obs.analyze import analyze_loader

        return analyze_loader(self)

    @property
    def provenance(self):
        """The attached :class:`~petastorm_tpu.obs.provenance
        .ProvenanceRecorder`, or None when ``provenance=`` was not passed."""
        return self._prov_rec

    @property
    def slo_engine(self):
        """The attached :class:`~petastorm_tpu.obs.slo.SloEngine`, or None
        when ``slos=`` was not passed."""
        return self._slo_engine

    @property
    def controller(self):
        """The attached :class:`~petastorm_tpu.control.Controller`, or None
        when ``controller=`` was not passed."""
        return self._controller

    def ctl_decisions(self):
        """The controller's decisions so far (ISSUE 13) — each a
        :class:`~petastorm_tpu.control.Decision` carrying the cause
        (``ctl_actuate``/``ctl_revert``/``ctl_freeze``), the knob's
        before/after values and the triggering window. Empty without
        ``controller=``."""
        return self._controller.decisions() if self._controller is not None \
            else []

    def slo_alerts(self):
        """Debounced SLO-breach/anomaly alerts so far (ISSUE 12) — each an
        :class:`~petastorm_tpu.obs.slo.SloAlert` carrying an attribution
        snapshot when provenance is on. Empty without ``slos=``."""
        return self._slo_engine.alerts() if self._slo_engine is not None \
            else []

    def _require_provenance(self):
        if self._prov_rec is None:
            raise ValueError(
                "DataLoader was built without provenance — pass "
                "provenance=True (or a ProvenanceRecorder, or set "
                "PTPU_PROVENANCE=1) to enable batch_provenance()/"
                "attribution_report()")
        return self._prov_rec

    def batch_provenance(self):
        """The most recently delivered batch's provenance (ISSUE 10): its
        contributing item records — spans across every pipeline seam and
        process, annotations (cache tier, hedges, retries, quarantine) — plus
        the batch-plane spans and the step gap. ``None`` before the first
        delivery. Requires ``provenance=``."""
        return self._require_provenance().last_batch()

    def attribution_report(self, tenant=None):
        """Fold the recorded batch window into a critical-path step-time
        attribution (:class:`~petastorm_tpu.obs.critical_path
        .AttributionReport`): per-site self seconds and shares on the
        critical path, step-gap p50/p99 split by cache tier and degradation
        cause, and the "your p99 batch spent N% in <site>" verdict — the
        refinement of :meth:`bottleneck_report` down to a concrete site.
        ``tenant=`` (ISSUE 18) narrows the batch window to batches whose
        items that tenant delivered. Requires ``provenance=``."""
        return self._require_provenance().report(tenant=tenant)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.stop()
        self.join()
        self.reader.stop()
        self.reader.join()
        if self._adopted_passthrough:
            # hand delivery materialization back to the reader: a reader
            # outliving this loader serves decoded batches again
            self.reader.keep_passthrough = False
            self._adopted_passthrough = False
        if self._adopted_ragged:
            self.reader.keep_ragged = False
            self._adopted_ragged = False
        self._transfer.close()
        if self._slo_engine is not None and self._slo_owned:
            # a loader-built engine stops evaluating on the sampler cadence
            # (alerts stay readable); a caller-supplied SHARED engine keeps
            # watching — a sibling pipeline may still be burning
            self._slo_engine.detach()
        if self._controller is not None and self._ctl_owned:
            # same ownership convention: a loader-built controller stops
            # actuating (decisions stay readable); a shared one is the
            # caller's to detach
            self._controller.detach()
        if self._obs is not None:
            self._obs.close()
        if self._prov_rec is not None and self._prov_owned:
            # a loader-built recorder releases the process-global slot here
            # (records stay readable — a post-exit attribution_report() still
            # works over the window); reader-owned recorders were disarmed by
            # reader.join() above, caller-supplied ones stay armed (theirs)
            self._prov_rec.disarm()
        if self._health is not None:
            monitor = self._health
            context_handle, stall_handle = self._health_handles or (None, None)
            if context_handle is not None:
                monitor.remove_context(context_handle)
            if stall_handle is not None:
                monitor.remove_stall_callback(stall_handle)
            self._health_handles = ()
            if self._health_owned:
                # a SHARED monitor (health=HealthMonitor(...)) stays running —
                # its owner tears it down; one the loader built is retired here
                monitor.stop()
            elif self._health_scope is not None:
                # shared monitor: retire this pipeline's scoped actors so
                # closed loader generations don't accumulate on it forever
                self._health_scope.close()


def _put_with_stop(q, item, stop_event):
    """Bounded-queue put that gives up once the loader is stopped: an untimed put can
    block forever when stop() wins the race for the slot freed by its own drain (the
    consumer is gone, nothing ever drains again). Returns False when stopped."""
    full = queue.Full  # bound early: may run during interpreter teardown
    while True:
        try:
            q.put(item, timeout=0.1)
            return True
        except full:
            if stop_event.is_set():
                return False


def _put_sentinel(q, stop_event):
    """Deliver the end-of-stream sentinel even when the consumer is slow: keep retrying
    until the put lands or the loader is stopped (a timed-out put must NOT drop the
    sentinel — the consumer would block forever on an empty queue)."""
    full = queue.Full  # bound early: may run during interpreter teardown
    while True:
        try:
            q.put(_SENTINEL, timeout=1)
            return
        except full:
            if stop_event.is_set():
                return


def _pad_ragged_columns(columns, pad_shapes):
    """Zero-pad ragged tensor columns to their declared max shape + a validity mask.

    Runs in the producer (before shuffling/batching) so downstream stages only ever
    see static shapes. 1-D rows pad with one vectorized scatter of their joined
    values; rows of higher rank pad one at a time."""
    columns = dict(columns)
    for name, target in pad_shapes.items():
        col = columns.get(name)
        if col is None:
            continue
        if isinstance(col, np.ndarray) and col.dtype != object:
            if col.shape[1:] == tuple(target):  # already uniform at the max: mask-only
                columns[name + "__mask"] = np.ones(col.shape, dtype=bool)
                continue
            col = list(col)  # uniform but below max: pad like the ragged case
        target = tuple(target)
        rows = [np.asarray(r) for r in col]
        dtype = rows[0].dtype if rows else np.float64
        if len(target) == 1 and all(r.ndim == 1 for r in rows):
            lengths = np.fromiter((len(r) for r in rows), np.int64, len(rows))
            over = np.flatnonzero(lengths > target[0])
            if len(over):
                i = int(over[0])
                raise ValueError(
                    "Row %d of field %r has shape %r exceeding declared pad max %r"
                    % (i, name, (int(lengths[i]),), target))
            mask = np.arange(target[0])[None, :] < lengths[:, None]
            out = np.zeros((len(rows),) + target, dtype=dtype)
            if rows:
                out[mask] = np.concatenate(rows)
        else:
            out = np.zeros((len(rows),) + target, dtype=dtype)
            mask = np.zeros((len(rows),) + target, dtype=bool)
            for i, r in enumerate(rows):
                if r.ndim != len(target):
                    raise ValueError(
                        "pad_shapes[%r]=%r has rank %d but row %d has rank %d"
                        % (name, target, len(target), i, r.ndim)
                    )
                if any(a > t for a, t in zip(r.shape, target)):
                    raise ValueError(
                        "Row %d of field %r has shape %r exceeding declared pad max %r"
                        % (i, name, r.shape, target)
                    )
                region = tuple(slice(0, s) for s in r.shape)
                out[i][region] = r
                mask[i][region] = True
        columns[name] = out
        columns[name + "__mask"] = mask
    return columns


def _resolve_pack(pack, shuffling_queue_capacity, device_shuffle_capacity, pad_shapes,
                  split_over_processes):
    """``DataLoader(pack=...)`` as ``(field, length)``, or None; raises for a
    malformed spec and for the options packing cannot be combined with."""
    if pack is None:
        return None
    if not isinstance(pack, dict) or set(pack) != {"field", "length"}:
        raise ValueError("pack= takes {'field': <list column>, 'length': <tokens a "
                         "row>}, got %r" % (pack,))
    field, length = pack["field"], pack["length"]
    if not isinstance(field, str) or isinstance(length, bool) \
            or not isinstance(length, (int, np.integer)) or length <= 0:
        raise ValueError("pack= needs a field name and a positive int length, got %r"
                         % (pack,))
    if shuffling_queue_capacity:
        raise ValueError(
            "pack= cannot be combined with shuffling_queue_capacity: the shuffling "
            "buffer holds documents back and releases them out of order, while the "
            "packer places the stream's documents in order and carries a part-filled "
            "batch between deliveries. Shuffle row groups in the reader "
            "(shuffle_row_groups=True) instead.")
    if device_shuffle_capacity:
        raise ValueError(
            "pack= cannot be combined with device_shuffle_capacity: the HBM exchange "
            "shuffle trades rows between batches, which undoes the placement order "
            "of the packed stream. Shuffle row groups in the reader instead.")
    if field in pad_shapes:
        raise ValueError(
            "pack= and pad_shapes both name %r: pad_shapes pads each document alone "
            "to a max shape, pack= packs the documents into shared rows. Choose one."
            % field)
    if split_over_processes:
        raise ValueError(
            "pack= packs in one process: under a sharding whose batch axis spans "
            "processes each process would pack its own rows and lay out only its own "
            "chunk table")
    return field, int(length)


def _resolve_local_batch(batch_size, sharding):
    """Rows this process feeds per global batch of ``batch_size`` (1 process → all).

    A global batch that does not divide over the sharding's batch axis raises
    (misconfiguration must not silently feed P×-larger batches). Under multi-process
    JAX, only a ``NamedSharding`` (or a sharding whose devices are all local) can be
    decomposed into per-process shares — a ``PositionalSharding``/GSPMD sharding
    spanning processes raises instead of silently feeding every process the GLOBAL
    batch and assembling wrong data (VERDICT r2 #5)."""
    try:
        import jax
        import jax.sharding as jsh
    except ImportError:  # jax optional for host-only use
        return batch_size
    if sharding is None or jax.process_count() == 1:
        return batch_size

    def _all_local(s):
        try:
            pi = jax.process_index()
            return all(d.process_index == pi for d in s.device_set)
        except Exception:  # noqa: BLE001 — unknown sharding type: treat as non-local
            return False

    def _reject(s):
        raise ValueError(
            "DataLoader cannot decompose the global batch across processes for %s: "
            "only NamedSharding exposes the mesh/axis structure needed to compute "
            "each process's local share. Use a NamedSharding over a Mesh (batch axis "
            "in PartitionSpec position 0), or shard the reader per process and pass "
            "a process-local sharding." % type(s).__name__
        )

    if isinstance(sharding, dict):  # per-field dict: use the first named sharding
        # EVERY non-named entry must be process-local — one decomposable field must
        # not grandfather in an undecomposable one beside it
        for s in sharding.values():
            if s is not None and not isinstance(s, jsh.NamedSharding) \
                    and not _all_local(s):
                _reject(s)
        named = [s for s in sharding.values() if isinstance(s, jsh.NamedSharding)]
        if not named:
            return batch_size  # every field placement is process-local
        sharding = named[0]
    if not isinstance(sharding, jsh.NamedSharding):
        if _all_local(sharding):
            return batch_size  # single-device/local placement: no decomposition needed
        _reject(sharding)
    from petastorm_tpu.parallel.mesh import local_batch_size

    spec0 = sharding.spec[0] if len(sharding.spec) else None
    if spec0 is None:
        return batch_size  # batch axis replicated: every process feeds all rows
    axes = (spec0,) if isinstance(spec0, str) else tuple(spec0)
    return local_batch_size(batch_size, sharding.mesh, batch_axes=axes)


class InMemDataLoader:
    """Epochs served entirely from device memory: load the dataset (or its shard) to
    HBM ONCE, then every batch is a single on-device permutation gather — zero host
    work, zero H2D after the fill.

    TPU-native analog of the reference's ``InMemBatchedDataLoader``
    (petastorm/pytorch.py ~L380), which re-collates host tensors per epoch; here the
    shuffle itself runs on device (one fused ``take`` per column), so epoch iteration
    costs no host CPU and no transfer — the right shape for small/medium datasets
    (MNIST-scale fine-tuning, eval sets) on big accelerators.

    Under multi-process JAX each process fills its own shard (pass a sharded reader,
    ``cur_shard=jax.process_index()``) and keeps it resident on ITS devices; every
    batch gathers each process's local share and assembles the global ``jax.Array``
    from the device-resident parts. ``batch_size`` stays GLOBAL; requires a
    decomposable ``NamedSharding`` and ``last_batch='drop'``; the per-epoch batch
    count is agreed once at fill time (allgather of local row counts — the only
    collective).

    Parameters
    ----------
    reader : Reader
        Source reader; consumed ONCE during construction (its ``num_epochs`` should be
        1). Device-decode staging columns are finished on device during the fill.
    batch_size : int
        Rows per yielded batch.
    num_epochs : int or None
        Epochs to serve; ``None`` = infinite.
    shuffle : bool
        Reshuffle every epoch with a fresh fold of ``seed`` (deterministic).
    sharding : jax.sharding.Sharding, optional
        Layout for the resident store AND the yielded batches (e.g. batch axis over a
        ``dp`` mesh axis).
    last_batch : {"drop", "partial"}
        Remainder policy per epoch (``pad`` is pointless here — resize the store).
        With ``sharding``, a ``partial`` tail batch is laid out per the sharding when
        its row count divides the batch axis, and yielded unsharded (default layout)
        otherwise — a pjit'd step with fixed ``in_shardings`` should use ``drop``.
    """

    def __init__(self, reader, batch_size, num_epochs=1, shuffle=True, seed=0,
                 sharding=None, last_batch="drop", device_transform=None,
                 device_decode_resize=None, trace=None):
        if last_batch not in ("drop", "partial"):
            raise ValueError("last_batch must be drop|partial, got %r" % last_batch)
        import jax
        import jax.numpy as jnp

        self.batch_size = int(batch_size)
        self.num_epochs = num_epochs
        self.shuffle = shuffle
        self.last_batch = last_batch
        self._seed = int(seed)
        self._device_transform = device_transform
        self._jitted_transform = None
        # fill: reuse the streaming DataLoader (handles staged on-device decode and the
        # sharding layout), then concatenate the chunks on device
        plan = getattr(reader, "_plan", None)
        if plan is not None and getattr(plan, "_num_epochs", 1) is None:
            raise ValueError(
                "InMemDataLoader consumes the reader ONCE to fill device memory; an "
                "infinite reader (num_epochs=None) would never finish the fill. Build "
                "the reader with num_epochs=1 and set epochs here."
            )
        #: multi-process: each process keeps ITS shard HBM-resident and every batch
        #: assembles a global jax.Array from the per-process device-resident gathers
        #: (same contract as per-rank InMemBatchedDataLoader under DDP, but the
        #: delivered batch is global). Requires a decomposable NamedSharding and
        #: last_batch='drop'; per-epoch batch count is agreed once at fill time via
        #: an allgather of local row counts.
        self._multiprocess = jax.process_count() > 1
        if self._multiprocess:
            if sharding is None:
                raise ValueError(
                    "multi-process InMemDataLoader requires a sharding (a "
                    "NamedSharding whose batch axis decomposes per process)")
            if last_batch != "drop":
                raise ValueError(
                    "multi-process InMemDataLoader supports last_batch='drop' only "
                    "(a ragged tail cannot assemble into a uniform global array)")
            self.local_batch_size = _resolve_local_batch(self.batch_size, sharding)
            if self.local_batch_size >= self.batch_size:
                # a replicated batch axis would assemble each process's DIFFERENT
                # shard rows as 'replicas' of one global array — silent corruption
                # (jax requires replica data to be identical and does not verify it)
                raise ValueError(
                    "multi-process InMemDataLoader requires a sharding whose batch "
                    "axis spans processes (each process contributes its shard); a "
                    "replicated batch axis would label divergent per-process shards "
                    "as replicas of the same array")
        else:
            self.local_batch_size = self.batch_size
        self._sharding = sharding
        self._trace = trace  # fill spans recorded via the inner DataLoader; gather
        # dispatch spans recorded per batch below
        chunks = []
        dropped = set()
        # fill UNSHARDED: chunk/partial-batch row counts rarely divide the batch axis;
        # the resident store and gathered batches are laid out below instead
        with DataLoader(reader, self.batch_size, sharding=None,
                        last_batch="partial", prefetch=2,
                        device_decode_resize=device_decode_resize,
                        trace=trace) as fill:
            for batch in fill:
                kept = {}
                for k, v in batch.items():
                    # host-only columns (strings/objects) cannot live in HBM — dropped
                    if isinstance(v, np.ndarray) and not _is_device_dtype(v):
                        dropped.add(k)
                    else:
                        kept[k] = v
                chunks.append(kept)
        if dropped:
            logger.warning("InMemDataLoader dropped host-only fields: %s",
                           sorted(dropped))
        if not chunks:
            raise ValueError("reader yielded no rows to load in memory")
        self._store = {
            k: jnp.concatenate([jnp.asarray(c[k]) for c in chunks], axis=0)
            for k in chunks[0]
        }
        self.rows = int(next(iter(self._store.values())).shape[0])
        if self._multiprocess:
            from jax.experimental import multihost_utils

            self._local_rows = self.rows
            all_rows = np.asarray(multihost_utils.process_allgather(
                np.array([self._local_rows], dtype=np.int64))).ravel()
            self._batches_per_epoch = int(all_rows.min()) // self.local_batch_size
            if self._batches_per_epoch == 0:
                raise ValueError(
                    "multi-process InMemDataLoader: some process holds only %d rows "
                    "— fewer than its local batch share %d; no full global batch "
                    "can be formed" % (int(all_rows.min()), self.local_batch_size))
            served = self._batches_per_epoch * self.local_batch_size
            if int(all_rows.max()) > served:
                logger.warning(
                    "InMemDataLoader shards are uneven (%d..%d rows/process): each "
                    "epoch serves %d rows/process; with shuffle=True the excluded "
                    "rows differ per epoch, with shuffle=False the SAME surplus "
                    "rows are never served — rebalance shards (shard_seed) or keep "
                    "shuffle on", int(all_rows.min()), int(all_rows.max()), served)
            self.rows = int(all_rows.sum())
            # the store stays PROCESS-LOCAL (addressable devices); the global layout
            # happens per batch from the already-device-resident gathers
        elif sharding is not None:
            # shard the resident store along the batch axis when the row count
            # divides; otherwise it stays on the default device and only the
            # gathered batches are laid out per the sharding
            try:
                self._store = {
                    k: jax.device_put(v, column_sharding(sharding, k, v))
                    for k, v in self._store.items()
                }
            except ValueError:
                logger.warning(
                    "InMemDataLoader store (%d rows) does not divide over the "
                    "sharding's batch axis; store kept unsharded", self.rows)

        def _gather(store, idx):
            return {k: v[idx] for k, v in store.items()}

        self._gather = jax.jit(_gather)
        #: (epoch, next batch within epoch) the NEXT yield will serve — the
        #: exact-resume cursor (epochs are deterministic by seed/epoch fold)
        self._pos = (0, 0)
        self._resume = None

    # -- exact resume (epochs are deterministic, so the cursor IS the state) -----------

    def state_dict(self):
        """Exact-resume cursor: ``(epoch, batch)`` of the next batch to serve, plus
        the stream-identity config. Epoch order is a pure function of
        ``seed``/``epoch`` (per process under multi-process JAX), so restoring the
        cursor into a same-config loader reproduces the stream EXACTLY-once — no
        replay at all, stronger than the streaming loader's row-group watermark.
        Duck-types for :mod:`petastorm_tpu.checkpoint` like the other loaders.

        A pending restored cursor (``load_state_dict`` before the first batch) is
        returned as-is — saving immediately after restoring must not forget the
        restore point. After a pass completes, the cursor points past its last
        epoch (an exhausted stream restores to an empty one — correct); a
        RE-iteration is a new pass and resets the cursor when it starts."""
        epoch, batch = self._resume if self._resume is not None else self._pos
        return {"inmem": True, "epoch": int(epoch), "batch": int(batch),
                "seed": self._seed, "shuffle": bool(self.shuffle),
                "rows": int(self.rows), "batch_size": int(self.batch_size),
                "last_batch": self.last_batch,
                "num_epochs": None if self.num_epochs is None
                else int(self.num_epochs)}

    def load_state_dict(self, state):
        """Resume a same-config loader at a saved cursor (before iterating)."""
        if not state.get("inmem"):
            raise ValueError(
                "not an InMemDataLoader state (checkpoint from a streaming loader/"
                "reader? restore it into the matching object)")
        mismatches = {
            k: (state.get(k), have) for k, have in (
                ("seed", self._seed), ("shuffle", bool(self.shuffle)),
                ("rows", int(self.rows)), ("batch_size", int(self.batch_size)),
                ("last_batch", self.last_batch),
                # a shorter num_epochs would silently serve NOTHING when the
                # cursor's epoch is past it — a different finite stream entirely
                ("num_epochs", self.num_epochs),
            ) if state.get(k) != have
        }
        if mismatches:
            raise ValueError(
                "InMemDataLoader state does not match this loader's stream config "
                "(saved vs built): %s — a different config is a different epoch "
                "stream, and resuming would serve wrong rows" % (mismatches,))
        self._resume = (int(state["epoch"]), int(state["batch"]))
        return self

    @property
    def cur_shard(self):
        """Per-process routing key for pod checkpoints (process index: each process
        serves its own resident shard)."""
        import jax

        return jax.process_index() if self._multiprocess else None

    def __len__(self):
        if self._multiprocess:
            return self._batches_per_epoch
        full, rem = divmod(self.rows, self.batch_size)
        return full + (1 if rem and self.last_batch == "partial" else 0)

    def __iter__(self):
        import jax
        import jax.numpy as jnp

        resume, self._resume = self._resume, None
        epoch = resume[0] if resume else 0
        skip = resume[1] if resume else 0  # batches to skip in the FIRST epoch only
        # a fresh pass resets the cursor: without this, a checkpoint taken early in
        # a RE-iteration would carry the previous pass's end-of-stream position
        self._pos = (epoch, skip)
        takes_key = False
        if self._device_transform is not None:
            import inspect

            try:
                takes_key = len(inspect.signature(
                    self._device_transform).parameters) >= 2
            except (TypeError, ValueError):
                takes_key = False
        while self.num_epochs is None or epoch < self.num_epochs:
            # absolute step (for the transform's rng fold) is position-derived so a
            # resumed stream folds the SAME keys an uninterrupted run would
            per_epoch = len(self)
            if self._multiprocess:
                yield from self._multiprocess_epoch(epoch, takes_key,
                                                    epoch * per_epoch, skip)
                epoch += 1
                skip = 0
                continue
            if self.shuffle:
                key = jax.random.fold_in(jax.random.PRNGKey(self._seed), epoch)
                perm = jax.random.permutation(key, self.rows)
            else:
                perm = jnp.arange(self.rows)
            for bidx, start in enumerate(range(0, self.rows, self.batch_size)):
                if bidx < skip:
                    continue
                idx = perm[start:start + self.batch_size]
                if len(idx) < self.batch_size and self.last_batch == "drop":
                    break
                t_g = time.perf_counter()
                batch = self._gather(self._store, idx)
                if self._sharding is not None:
                    # shard the short final batch too when its row count divides the
                    # sharding's batch axis; otherwise it stays on the gather's layout
                    # (documented: a pjit'd step with fixed in_shardings will see one
                    # differently-laid-out tail batch — use last_batch='drop' there).
                    # Divisibility is checked explicitly — a blanket except would
                    # misreport genuine sharding bugs (rank/spec mismatch) as a
                    # tail-batch artifact and transfer columns only to discard them.
                    if len(idx) % batch_axis_shard_count(self._sharding) == 0:
                        batch = {k: jax.device_put(v, column_sharding(self._sharding, k, v))
                                 for k, v in batch.items()}
                    else:
                        logger.warning(
                            "InMemDataLoader: final partial batch (%d rows) does not "
                            "divide the sharding's batch axis; yielded unsharded",
                            len(idx))
                if self._trace is not None:
                    # span covers gather + layout dispatch — the same serving work
                    # the multi-process path's span covers (gather + assembly)
                    self._trace.add("inmem.gather", t_g, time.perf_counter() - t_g)
                batch = self._apply_transform(batch, epoch * per_epoch + bidx,
                                              takes_key)
                self._pos = (epoch, bidx + 1)
                yield batch
            epoch += 1
            skip = 0

    def _multiprocess_epoch(self, epoch, takes_key, step0, skip=0):
        """One epoch under multi-process JAX: per-process local permutation gathers,
        each assembled into a global jax.Array from the device-resident local share
        (no host round trip — same path the streaming loader's decode assembly uses)."""
        import jax
        import jax.numpy as jnp

        if self.shuffle:
            # fold the process index so shard orders decorrelate across processes
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self._seed), epoch),
                jax.process_index() + 1)
            perm = jax.random.permutation(key, self._local_rows)
        else:
            perm = jnp.arange(self._local_rows)
        for b in range(skip, self._batches_per_epoch):
            idx = perm[b * self.local_batch_size:(b + 1) * self.local_batch_size]
            t_g = time.perf_counter()
            local = self._gather(self._store, idx)
            batch = {}
            for k, v in local.items():
                s = column_sharding(self._sharding, k, v)
                if s is None:
                    batch[k] = v  # field without a declared layout stays local
                else:
                    batch[k] = jax.make_array_from_process_local_data(s, v)
            if self._trace is not None:
                # gather + global assembly dispatch: the per-batch serving cost
                self._trace.add("inmem.gather", t_g, time.perf_counter() - t_g)
            batch = self._apply_transform(batch, step0 + b, takes_key)
            self._pos = (epoch, b + 1)
            yield batch

    def _apply_transform(self, batch, step, takes_key):
        if self._device_transform is None:
            return batch
        import jax

        if self._jitted_transform is None:
            self._jitted_transform = jax.jit(self._device_transform)
        if takes_key:
            tkey = jax.random.fold_in(jax.random.PRNGKey(self._seed + 1), step)
            return self._jitted_transform(batch, tkey)
        return self._jitted_transform(batch)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self._store = None  # release HBM


_UNSET = object()

#: DataLoader keyword parameters make_dataloader forwards when explicitly given —
#: defaults stay defined ONCE, on DataLoader.__init__ (they'd silently drift if
#: re-stated here).
_LOADER_OPTS = ("last_batch", "device_transform", "prefetch", "pad_shapes",
                "device_shuffle_capacity", "to_device", "host_queue_size",
                "device_decode_resize", "trace", "metrics", "health", "staging",
                "provenance", "slos", "controller")


def make_dataloader(dataset_url_or_urls, batch_size, sharding=None, num_epochs=1,
                    shuffling_queue_capacity=0, reader_factory=None,
                    last_batch=_UNSET, device_transform=_UNSET, prefetch=_UNSET,
                    pad_shapes=_UNSET, device_shuffle_capacity=_UNSET,
                    to_device=_UNSET, host_queue_size=_UNSET,
                    device_decode_resize=_UNSET, trace=_UNSET, metrics=_UNSET,
                    health=_UNSET, staging=_UNSET, provenance=_UNSET,
                    slos=_UNSET, controller=_UNSET, **reader_kwargs):
    """One-call convenience: ``make_batch_reader`` + :class:`DataLoader`.

    ``reader_kwargs`` pass through to :func:`petastorm_tpu.reader.make_batch_reader`
    (or ``reader_factory`` when given); the explicit loader parameters mirror
    :class:`DataLoader` (defaults are DataLoader's — only explicitly-passed values
    are forwarded). Under multi-process JAX, ``cur_shard``/``shard_count`` default
    to ``jax.process_index()``/``jax.process_count()``.
    """
    from petastorm_tpu.reader import make_batch_reader

    factory = reader_factory or make_batch_reader
    if "cur_shard" not in reader_kwargs:
        try:
            import jax

            if jax.process_count() > 1:
                reader_kwargs["cur_shard"] = jax.process_index()
                reader_kwargs["shard_count"] = jax.process_count()
        except Exception as e:  # noqa: BLE001 — jax optional for host-only use
            logger.debug("jax process topology unavailable (%s); reader "
                         "sharding left to explicit kwargs", e)
    reader = factory(dataset_url_or_urls, num_epochs=num_epochs, **reader_kwargs)
    seed = reader_kwargs.get("seed")
    if seed is None:
        seed = reader_kwargs.get("shard_seed")
    passed = locals()
    loader_kwargs = {name: passed[name] for name in _LOADER_OPTS
                     if passed[name] is not _UNSET}
    return DataLoader(reader, batch_size, sharding=sharding,
                      shuffling_queue_capacity=shuffling_queue_capacity, seed=seed,
                      **loader_kwargs)
