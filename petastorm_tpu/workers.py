"""Worker executors: pull row-group work items from a plan, run a worker, stream results.

Functional parity with the reference worker-pool layer (petastorm/workers_pool/: ``ThreadPool``
thread_pool.py ~L60, ``ProcessPool`` process_pool.py ~L60 + ZeroMQ sockets, ``DummyPool``
dummy_pool.py ~L30, ``ConcurrentVentilator`` ventilator.py ~L60), redesigned per SURVEY.md §3.2:

- No ZeroMQ and no ventilator thread. Backpressure is a bounded results queue; the "ventilator"
  is the (possibly infinite, resumable) :class:`petastorm_tpu.plan.EpochPlan` pulled lazily
  through a :class:`PullDispatcher` — bounded per-worker claims (the readahead layer's
  lookahead window, ISSUE 4) with work stealing when the plan runs dry. Threads are the
  default pool — Arrow IO and cv2 decode release the GIL, and the
  heavy decode moves on-device anyway (Pallas), so forked processes buy little and cost pickling.
- ``ProcessPoolExecutor`` is kept for CPU-hungry user ``TransformSpec`` functions: workers are
  initialized once per child (no per-task worker pickling) and in-flight tasks are capped for
  backpressure, mirroring the reference's ``max_ventilation_queue_size``.

Contract: ``executor.start(worker, plan)`` then iterate ``executor.results()``; worker is a
picklable callable ``worker(item) -> result``; exceptions in workers propagate to the consumer;
``stop()``/``join()`` mirror the reference pool API.
"""
from __future__ import annotations

import logging
import queue
import threading
from collections import deque

from petastorm_tpu import chaos as _chaos
from petastorm_tpu.obs import provenance as _prov
from petastorm_tpu.errors import TimeoutWaitingForResultError, WorkerDiedError
from petastorm_tpu.recovery import QuarantinedItem, RecoveryOptions
from petastorm_tpu.trace import profiler_span

logger = logging.getLogger(__name__)

_DONE = object()


_steal_counter = None


def _result_rows(result):
    """Rows in a worker's result: a columnar dict's column length, or the
    length of a row list (0 for anything else), in the payload of a result
    the reader tagged ``(epoch, ordinal, payload)``."""
    if isinstance(result, tuple) and len(result) == 3:
        result = result[2]
    if isinstance(result, dict):
        return len(next(iter(result.values()))) if result else 0
    return len(result) if isinstance(result, list) else 0


def _count_steal():
    """Bump ``ptpu_io_steals_total`` (resolved once per process)."""
    global _steal_counter
    counter = _steal_counter
    if counter is None:
        from petastorm_tpu.obs.metrics import default_registry

        counter = _steal_counter = default_registry().counter(
            "ptpu_io_steals_total",
            help="claimed plan items taken from a busy worker by an idle one")
    counter.inc()


class PullDispatcher:
    """Pull-based piece dispatch over a shared plan: bounded per-worker claims
    plus work stealing (ISSUE 4).

    Each worker claims up to ``1 + lookahead`` upcoming plan items into its own
    deque — the lookahead is what the readahead layer prefetches, so the items a
    worker announces as "next" really are the ones it will process. When the
    plan runs dry an idle worker steals from the TAIL of the longest peer claim
    (the piece its owner would reach last), so a worker stuck on one slow piece
    no longer strands the rest of its claim behind it. With ``lookahead=0`` and
    no stealing this degenerates to exactly the old shared ``next(plan_iter)``
    under a lock.

    Plan order is preserved at dispatch: claims are filled strictly in plan
    order and consumed FIFO; only completion order can differ (it always could
    — workers finish out of order), which the Reader's consumed-ordinal
    bookkeeping and the loader's checkpoint watermark already handle.
    """

    def __init__(self, plan, workers_count, lookahead=0, stealing=True,
                 recorder=None):
        self._iter = iter(plan)
        self._lock = threading.Lock()
        self._claims = [deque() for _ in range(max(1, workers_count))]
        self._exhausted = False
        self._lookahead = max(0, int(lookahead))
        self._stealing = bool(stealing)
        #: items handed back by a retiring worker (live fleet shrink, ISSUE
        #: 13): refilled into claims BEFORE the plan iterator — they were
        #: claimed earlier in plan order than anything still unclaimed
        self._returned = deque()
        #: optional petastorm_tpu.obs.flight.FlightRecorder — steal decisions
        #: ride in the health layer's event ring (None = no recording)
        self._recorder = recorder
        self.steals = 0

    def next(self, worker_idx):
        """Claim the next item for ``worker_idx``: ``(item, upcoming)`` where
        ``upcoming`` is the rest of this worker's claim (the prefetch hint), or
        ``None`` when no work is left anywhere."""
        with self._lock:
            claim = self._claims[worker_idx]
            self._fill(claim, 1 + self._lookahead)
            if not claim and self._stealing:
                victim = max((c for c in self._claims if c), key=len, default=None)
                if victim is not None:
                    claim.append(victim.pop())  # tail: the victim's furthest item
                    self.steals += 1
                    _count_steal()
                    if self._recorder is not None:
                        self._recorder.record("steal", thief=worker_idx,
                                              victim_len=len(victim))
            if not claim:
                return None
            item = claim.popleft()  # the fill above keeps the hint window full
            return item, tuple(claim)

    def set_recorder(self, recorder):
        """Attach/replace the flight recorder mid-stream (the usual order:
        the executor starts during ``Reader.__init__``, the health monitor
        arrives later via ``DataLoader`` → ``reader.set_health``)."""
        with self._lock:
            self._recorder = recorder

    def set_lookahead(self, lookahead):
        """Retune the per-worker claim window live (ISSUE 13): the claim IS
        the readahead hint window, so a controller growing the prefetch depth
        must widen the hints with it or the deeper pool never sees more than
        the old window's worth of upcoming items."""
        with self._lock:
            self._lookahead = max(0, int(lookahead))

    def ensure_workers(self, workers_count):
        """Grow the claim table to at least ``workers_count`` slots (live
        fleet grow — ISSUE 13). Never shrinks: a retiring worker's slot stays
        (empty) so surviving indices keep their claims."""
        with self._lock:
            while len(self._claims) < workers_count:
                self._claims.append(deque())

    def withdraw(self, worker_idx):
        """Return ``worker_idx``'s unprocessed claim to the pool (live fleet
        shrink): the items refill other workers' claims before the plan
        iterator, so a drained worker loses no work and duplicates none.
        Returns the number of items handed back."""
        with self._lock:
            claim = self._claims[worker_idx]
            n = len(claim)
            self._returned.extend(claim)
            claim.clear()
        return n

    def return_items(self, items):
        """Hand specific already-dispatched items back to the pool (the data
        service's wire-lease requeue seam, ISSUE 19): a dead link's un-acked
        lease, a transiently failed decode, or a re-attached trainer's
        evicted payload re-enters dispatch ahead of the plan iterator — the
        same no-loss/no-duplicate discipline as :meth:`withdraw`, for items
        that had already left their claim deque."""
        with self._lock:
            self._returned.extend(items)
        return len(items)

    def has_work(self):
        """Is anything left to dispatch — handed-back items, claimed items,
        or an unexhausted plan? The executors' last-worker exit gate: a
        retiring worker may hand its claim back AFTER the surviving peers
        already saw an empty dispatcher and exited, and posting the
        end-of-stream marker over those stranded items would silently drop
        rows (the resize contract is byte-identical delivery)."""
        with self._lock:
            return bool(self._returned) or not self._exhausted \
                or any(self._claims)

    def _fill(self, claim, target):
        # caller MUST hold self._lock (all call sites do — the analyzer
        # cannot see cross-method lock ownership)
        while len(claim) < target:
            if self._returned:
                claim.append(self._returned.popleft())  # graftlint: disable=GL-C001
                continue
            if self._exhausted:
                break
            try:
                claim.append(next(self._iter))
            except StopIteration:
                self._exhausted = True  # graftlint: disable=GL-C001 (caller holds self._lock)

    def stats(self):
        return {"steals": self.steals}


class _ExcResult:
    def __init__(self, exc):
        self.exc = exc


def _iter_results(results_q, stop_event, timeout, stop_fn, on_truncated=None):
    """Shared results-drain loop for the threaded/process pools.

    Ends on the ``_DONE`` marker, re-raises worker exceptions (stopping the pool
    first), raises :class:`TimeoutWaitingForResultError` when nothing arrives
    within ``timeout`` — and returns PROMPTLY once ``stop_event`` is set and the
    queue is empty. The prompt return matters: ``stop()`` drains the results queue
    (including a ``_DONE`` already posted), so a consumer on ANOTHER thread that
    was blocked in ``get()`` at stop time — e.g. a tf.data generator thread being
    finalized while the main thread tears the reader down — used to sleep out the
    full ``results_timeout_s`` (the flaky exactly-300.07s ``test_tf_tensors_eager``
    hang, VERDICT r4 #7).

    The stop-event return is a TRUNCATION, not exhaustion: ``on_truncated`` fires
    on that branch (and only that branch) so the executor can mark the stream as
    aborted — ``Reader.__next__`` must not flag ``last_row_consumed`` when the
    stream ended because somebody called ``stop()`` mid-pass (ADVICE r5)."""
    import time

    deadline = time.monotonic() + timeout
    while True:
        try:
            value = results_q.get(timeout=0.2)
        except queue.Empty:
            if stop_event.is_set():
                if on_truncated is not None:
                    on_truncated()
                return  # stopped: the stream is over for this consumer
            if time.monotonic() > deadline:
                raise TimeoutWaitingForResultError(
                    "No worker result within %.0fs" % timeout
                ) from None
            continue
        if value is _DONE:
            # a _DONE that lands AFTER stop() is ambiguous: the stop-drain may
            # have discarded results ahead of it, so the stream cannot be called
            # fully consumed (the marker races the drain — workers re-post it)
            if stop_event.is_set() and on_truncated is not None:
                on_truncated()
            return
        if isinstance(value, _ExcResult):
            stop_fn()
            raise value.exc
        yield value
        # fresh budget per consumer request (matching the old per-get semantics):
        # time the CONSUMER spent between next() calls must not count against the
        # worker-result timeout
        deadline = time.monotonic() + timeout


class ExecutorBase:
    #: True when the result stream ended because ``stop()`` aborted it mid-pass
    #: rather than because the plan was exhausted (consumers use it to keep
    #: completion flags like ``Reader.last_row_consumed`` truthful)
    truncated = False

    #: optional petastorm_tpu.obs.health.HealthMonitor (ISSUE 5): worker
    #: threads / pool drivers register heartbeats and per-worker latency on it
    #: (None = disabled, one is-None check per loop iteration)
    _health = None

    #: optional petastorm_tpu.obs.provenance.ProvenanceRecorder (ISSUE 10):
    #: pool drivers record per-item wire spans and merge child-piggybacked
    #: item spans onto it (thread/dummy pools need no executor-side state —
    #: their worker threads feed the armed module-level collector directly)
    _prov = None

    def set_provenance(self, recorder):
        """Attach a provenance recorder (the Reader wires this; attachable
        mid-stream like ``set_health`` — drivers resolve it per item)."""
        self._prov = recorder

    def set_health(self, monitor):
        """Attach a :class:`petastorm_tpu.obs.health.HealthMonitor`: workers
        heartbeat per work item (busy vs backpressure-wait states), the
        dispatcher records steal events, and — on the process pool — children
        gain the stack-dump hook the stall watchdog collects evidence through.
        Attachable mid-stream: workers pick it up at their next loop pass, and
        an already-running dispatcher (the executor starts in
        ``Reader.__init__``, before the loader can attach health) is rewired
        to the monitor's flight ring here."""
        self._health = monitor
        dispatch = getattr(self, "_dispatch", None)
        if dispatch is not None:
            dispatch.set_recorder(monitor.flight if monitor is not None
                                  else None)

    def start(self, worker, plan):
        raise NotImplementedError

    def results(self):
        """Generator of worker results; raises worker exceptions; ends when plan exhausted."""
        raise NotImplementedError

    def _mark_truncated(self):
        self.truncated = True

    def _drain_results(self):
        """Shared ``results()`` body for the queue-backed pools (thread/process):
        one copy of the drain/timeout/truncation wiring."""
        return _iter_results(self._results, self._stop_event, self._timeout,
                             self.stop, on_truncated=self._mark_truncated)

    def stop(self):
        pass

    def join(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.stop()
        self.join()


class SyncExecutor(ExecutorBase):
    """Synchronous in-process execution (reference DummyPool): deterministic, for tests/debug.

    Readahead still applies (``lookahead > 0`` and a worker with ``prefetch``):
    the upcoming plan items come from ``plan.peek`` — the single consumer keeps
    its deterministic order while the IO pool reads ahead of it."""

    def __init__(self, lookahead=0, recovery=None, **_ignored):
        self._worker = None
        self._plan = None
        self._stopped = False
        self._lookahead = max(0, int(lookahead))
        self._recovery = RecoveryOptions.normalize(recovery)

    def set_lookahead(self, lookahead):
        """Live lookahead retune (ISSUE 13): ``results()`` reads the value
        per item, so the next iteration peeks the new window."""
        self._lookahead = max(0, int(lookahead))

    def start(self, worker, plan):
        self._worker = worker
        self._plan = plan
        self.truncated = False

    def results(self):
        prefetch = getattr(self._worker, "prefetch", None)
        peek = getattr(self._plan, "peek", None)
        recovery = self._recovery
        for item in self._plan:
            if self._stopped:
                self.truncated = True
                return
            if prefetch is not None and peek is not None and self._lookahead:
                upcoming = peek(self._lookahead)
                if upcoming:
                    prefetch(upcoming)
            attempts = 0
            result = None
            if _prov.ACTIVE is not None:
                _prov.begin_item(item)
            # end_item runs BEFORE the yield below: a generator suspends at
            # yield, and holding the item context open across the consumer's
            # turn would misattribute its spans to this item
            try:
                while True:
                    try:
                        if _chaos.ACTIVE is not None:
                            _chaos.ACTIVE.hit("worker.item",
                                              key=_chaos.item_key(item))
                        result = self._worker(item)
                    except Exception as e:  # noqa: BLE001 — policy-classified
                        attempts += 1
                        if not recovery.quarantine:
                            raise
                        if attempts >= recovery.poison_attempts:
                            result = QuarantinedItem(item, e, attempts)
                            break
                        continue  # retry the item in place
                    break
            finally:
                if _prov.ACTIVE is not None:
                    _prov.end_item()
            yield result

    def stop(self):
        self._stopped = True


class ThreadExecutor(ExecutorBase):
    """N threads pulling work items from the shared plan through a
    :class:`PullDispatcher` (bounded claims + work stealing); bounded results
    queue = backpressure."""

    def __init__(self, workers_count=4, results_queue_size=16, results_timeout_s=300.0,
                 lookahead=0, work_stealing=True, recovery=None, **_ignored):
        self._workers_count = workers_count
        self._queue_size = results_queue_size
        self._timeout = results_timeout_s
        self._lookahead = lookahead
        self._stealing = work_stealing
        self._recovery = RecoveryOptions.normalize(recovery)
        self._threads = []
        self._results = None
        self._stop_event = threading.Event()
        self._dispatch = None
        self._active = 0
        self._active_lock = threading.Lock()
        # live fleet-resize state (ISSUE 13), all under _active_lock
        self._target = workers_count   # intended fleet size
        self._retire = 0               # workers asked to drain and exit
        self._next_idx = workers_count
        self._worker_obj = None

    def start(self, worker, plan):
        self._results = queue.Queue(maxsize=self._queue_size)
        self._stop_event.clear()
        self.truncated = False
        self._worker_obj = worker
        monitor = self._health
        self._dispatch = PullDispatcher(
            plan, self._workers_count, lookahead=self._lookahead,
            stealing=self._stealing,
            recorder=monitor.flight if monitor is not None else None)
        with self._active_lock:
            self._active = self._workers_count
            self._target = self._workers_count
            self._retire = 0
            self._next_idx = self._workers_count
        for i in range(self._workers_count):
            t = threading.Thread(
                target=self._run_worker, args=(worker, self._dispatch, i),
                daemon=True, name="ptpu-worker-%d" % i,
            )
            t.start()
            self._threads.append(t)

    def _should_retire(self):
        """Claim one pending retirement (live shrink): checked by workers
        BETWEEN items only — a shrink drains, it never kills mid-item."""
        with self._active_lock:
            if self._retire > 0:
                self._retire -= 1
                return True
            return False

    def set_lookahead(self, lookahead):
        """Live dispatch-lookahead retune (rides with the readahead-depth
        knob — see :meth:`PullDispatcher.set_lookahead`)."""
        self._lookahead = max(0, int(lookahead))
        dispatch = self._dispatch
        if dispatch is not None:
            dispatch.set_lookahead(self._lookahead)

    @property
    def alive_workers(self):
        """Workers currently running (retiring ones still count until they
        drain out). Lock-free read: collectors poll this from other
        threads, and an int read is atomic."""
        return self._active

    @property
    def target_workers(self):
        return self._target

    def resize(self, workers_count):
        """Grow/shrink the worker fleet LIVE (ISSUE 13). Grow spawns fresh
        worker threads against the running dispatcher; shrink queues
        retirements that draining workers pick up between items — their
        unprocessed claims return to the dispatcher, so the delivered row
        set (and the consumed-ordinal watermark) is byte-identical to an
        un-resized run. A no-op once the stream has finished. Returns the
        applied target."""
        n = max(1, int(workers_count))
        dispatch = self._dispatch
        to_start = []
        with self._active_lock:
            if dispatch is None or self._active == 0:
                return self._target  # not started / already drained
            if n > self._target:
                grow = n - self._target
                cancelled = min(grow, self._retire)
                self._retire -= cancelled  # un-retire before spawning anew
                for _ in range(grow - cancelled):
                    to_start.append(self._next_idx)
                    self._next_idx += 1
                self._active += len(to_start)
            elif n < self._target:
                self._retire += self._target - n
            self._target = n
            next_idx = self._next_idx
        if to_start:
            dispatch.ensure_workers(next_idx)
            for idx in to_start:
                t = threading.Thread(
                    target=self._run_worker,
                    args=(self._worker_obj, dispatch, idx),
                    daemon=True, name="ptpu-worker-%d" % idx)
                t.start()
                self._threads.append(t)
        return n

    def _run_worker(self, worker, dispatch, idx):
        import time

        prefetch = getattr(worker, "prefetch", None)
        hb = None
        worker_fatal = False  # a fatal exit must never trigger the rescue gate
        try:
            while not self._stop_event.is_set():
                if self._should_retire():
                    # live shrink: hand the unprocessed claim back (others
                    # pick it up before the plan iterator) and drain out
                    dispatch.withdraw(idx)
                    break
                # health is resolved per pass, so a monitor attached after
                # start() (the loader wires the reader post-construction)
                # still instruments the rest of the stream
                monitor = self._health
                if monitor is not None and hb is None:
                    hb = monitor.register("worker.thread-%d" % idx, "worker")
                if hb is not None:
                    hb.wait("claim")  # an exhausted plan is idleness, not a stall
                claim = dispatch.next(idx)
                if claim is None:
                    break
                item, upcoming = claim
                if prefetch is not None and upcoming:
                    prefetch(upcoming)  # swallows its own failures (degradation-logged)
                if hb is not None:
                    hb.beat("working")
                t0 = time.perf_counter() if monitor is not None else 0.0
                recovery = self._recovery
                attempts = 0
                fatal = False
                result = None
                if _prov.ACTIVE is not None:
                    _prov.begin_item(item)
                try:
                    while True:  # item attempts (poison-quarantine policy)
                        try:
                            if _chaos.ACTIVE is not None:
                                _chaos.ACTIVE.hit("worker.item",
                                                  key=_chaos.item_key(item))
                            with profiler_span("ptpu.pool.item") as span:
                                result = worker(item)
                                if span is not None:
                                    span.set_metadata(rows=_result_rows(result))
                        except Exception as e:  # noqa: BLE001 — classified
                            attempts += 1
                            if not recovery.quarantine:
                                self._put(_ExcResult(e))  # to the consumer
                                fatal = True
                                break
                            if attempts >= recovery.poison_attempts:
                                result = QuarantinedItem(item, e, attempts)
                                break
                            continue  # retry the item in place
                        break
                finally:
                    if _prov.ACTIVE is not None:
                        _prov.end_item()
                if fatal:
                    worker_fatal = True
                    break
                if monitor is not None:
                    # per-worker latency histogram: the straggler detector's input
                    monitor.observe_worker(idx, time.perf_counter() - t0)
                if hb is not None:
                    hb.wait("results_put")  # a full results queue = backpressure
                self._put(result)
        finally:
            if hb is not None:
                hb.done()
            self._retire_worker(worker, dispatch, worker_fatal)

    def _retire_worker(self, worker, dispatch, fatal):
        """One worker's exit gate: decrement the fleet count and post the
        end-of-stream marker when this was the LAST worker — unless the
        dispatcher still holds work. That happens in exactly one (rare) race:
        a retiring worker hands its claim back AFTER the surviving peers
        already saw an empty dispatcher and exited; the last decrementer is
        the only actor that observes the strand atomically (the withdraw
        precedes its decrement in program order), so it spawns a rescue
        worker instead of declaring the stream complete over undelivered
        rows."""
        rescue_idx = None
        with self._active_lock:
            self._active -= 1
            if self._active == 0 and not fatal \
                    and not self._stop_event.is_set() and dispatch.has_work():
                self._active += 1  # the rescue worker's slot, reserved now
                rescue_idx = self._next_idx
                self._next_idx += 1
            last = self._active == 0
        if rescue_idx is not None:
            try:
                dispatch.ensure_workers(rescue_idx + 1)
                t = threading.Thread(
                    target=self._run_worker, args=(worker, dispatch,
                                                   rescue_idx),
                    daemon=True, name="ptpu-worker-%d" % rescue_idx)
                t.start()
                self._threads.append(t)
                return
            except Exception as e:  # noqa: BLE001 — degrade to stream end
                from petastorm_tpu.obs.log import degradation

                degradation(
                    "ctl_rescue_failed",
                    "stranded-claim rescue worker could not start (%s); the "
                    "handed-back items are LOST for this pass", e, once=False)
                with self._active_lock:
                    self._active -= 1
                    last = self._active == 0
        if last:
            # OUTSIDE the lock: _put blocks on a full results queue, and
            # a blocked holder would deadlock any reader of the fleet
            # gauges (the controller's collector) on the consumer thread
            self._put(_DONE)

    def dispatch_stats(self):
        """Work-stealing gauges for ``Reader.io_stats()``."""
        dispatch = self._dispatch
        return dispatch.stats() if dispatch is not None else {}

    def _put(self, value):
        # Even the _DONE marker yields to a SET stop event: the consumer is the one
        # who sets it, and it never reads results afterwards — spinning until the
        # full queue drains would park the last worker for join()'s whole timeout
        # (results_timeout_s) on every stop-mid-stream teardown.
        while True:
            try:
                self._results.put(value, timeout=0.1)
                return
            except queue.Full:
                if self._stop_event.is_set():
                    return

    def results(self):
        return self._drain_results()

    def stop(self):
        self._stop_event.set()
        # drain so blocked workers can exit
        try:
            while True:
                self._results.get_nowait()
        except (queue.Empty, AttributeError):
            pass

    def join(self):
        import time

        deadline = time.monotonic() + self._timeout  # shared across threads, not per-thread
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                from petastorm_tpu.obs.log import degradation

                degradation(
                    "thread_join_timeout",
                    "Worker thread %s still alive after %.0fs join (blocked in IO?); "
                    "it will exit at its next stop-event check without publishing",
                    t.name, self._timeout, once=False,
                )
        self._threads = []


# -- process pool ----------------------------------------------------------------------


class ProcessExecutor(ExecutorBase):
    """Multiprocess execution for CPU-bound workers (GIL-holding user transforms).

    Children are CLEAN interpreters started via ``python -m petastorm_tpu._child_worker``
    (reference design: exec_in_new_process + zmq, process_pool.py ~L60): no re-import of the
    user's ``__main__`` (multiprocessing spawn/forkserver fork-bombs unguarded scripts) and no
    fork of a threaded parent (JAX deadlock hazard). The worker is pickled once per child;
    per-task traffic is (item, result) over a unix socket. One driver thread per child gives
    one-item-in-flight-per-child backpressure plus the bounded results queue.

    With a ``serializer`` from the ``shm`` family the result payloads do NOT ride the
    socket: ``start()`` creates a :class:`petastorm_tpu.parallel.shm_ring.SlabRing`,
    each driver thread acquires a slab and grants it to its child together with the
    work item, and the child writes the serialized frames straight into the slab —
    only a small descriptor crosses the socket. Items whose payload exceeds the slab
    (or that find the ring momentarily empty) fall back to the socket wire per item;
    platforms without working shared memory degrade the whole pool to the socket
    wire with a warn-once. ``join()`` unlinks every slab — a pool can never leak
    ``/dev/shm`` segments, whatever its children did (SIGKILL mid-write included).
    """

    def __init__(self, workers_count=4, results_queue_size=16, results_timeout_s=300.0,
                 serializer="pickle", worker_respawns=None, shm_slab_bytes=None,
                 shm_slabs=None, lookahead=0, work_stealing=True, recovery=None,
                 transport=None, **_ignored):
        import os

        from petastorm_tpu.transport import normalize_transport

        self._workers_count = workers_count
        self._queue_size = results_queue_size
        self._timeout = results_timeout_s
        self._lookahead = lookahead
        self._stealing = work_stealing
        self._dispatch = None
        self._serializer_name = serializer
        from petastorm_tpu.serializers import make_serializer

        self._serializer = make_serializer(serializer)
        #: shm wire config (ignored for socket serializers): slab size defaults to
        #: 32 MB — comfortably a decoded row-group batch; oversized payloads fall
        #: back per item. PTPU_SHM_SLAB_BYTES tunes it through the reader factories
        #: without new kwargs at every layer.
        self._shm_slab_bytes = int(shm_slab_bytes
                                   or os.environ.get("PTPU_SHM_SLAB_BYTES", 0)
                                   or (32 << 20))
        self._shm_slabs = shm_slabs
        self._ring = None
        self._shm_unavailable = False
        self._tracer = None
        #: pool wire transport (ISSUE 15): 'pipe' (the default — today's unix
        #: socket, byte-identical) or 'tcp' (framed crc-trailered loopback/LAN
        #: sockets with reconnect + heartbeats; also via PTPU_TRANSPORT). The
        #: tcp hub and the shared authkey/token live for the pool's lifetime;
        #: a tcp setup failure degrades the pool back to 'pipe'.
        self._transport_name = normalize_transport(transport)
        self._hub = None
        self._tenant_label = None
        self._authkey = None
        self._session_counter = 0
        self._procs = []
        self._conns = []
        self._threads = []
        self._results = None
        self._stop_event = threading.Event()
        self._active = 0
        self._active_lock = threading.Lock()
        # live fleet-resize state (ISSUE 13), all under _active_lock
        self._target = workers_count
        self._retire = 0
        self._next_idx = workers_count
        self._tmpdir = None
        #: Elastic recovery (no reference analog — SURVEY §6: a worker death kills the
        #: read there): a child that dies mid-item is replaced by a fresh clean
        #: interpreter and the in-flight item re-dispatched, up to this many times per
        #: pool lifetime. 0 restores fail-fast. Bounded so a crash loop still
        #: surfaces; a single poison item (one that reliably kills children,
        #: e.g. OOM) can additionally be SKIPPED instead of burning the budget
        #: via ``RecoveryOptions(on_poison="quarantine")`` (ISSUE 7) — after
        #: ``poison_attempts`` failures of one plan item the pool emits a
        #: :class:`~petastorm_tpu.recovery.QuarantinedItem` marker, respawns
        #: the child WITHOUT charging the budget (the item will not be retried,
        #: so no crash-loop risk), and moves on.
        self._recovery = RecoveryOptions.normalize(recovery)
        self._respawn_budget = int(worker_respawns) if worker_respawns is not None \
            else self._recovery.worker_respawns
        self._respawn_lock = threading.Lock()
        self._spawn_counter = 0
        self._worker = None
        self._child_env = None
        #: driver idx -> live child Popen (maintained across respawns, under
        #: the respawn lock): the stall healer's kill target, and how a dead
        #: child's evidence is tied to the driver that owned it
        self._child_by_idx = {}
        #: driver idx -> failures of the CURRENT in-flight item so far — lets
        #: the healer predict whether a kill can be absorbed (quarantine
        #: threshold reached) before it pulls the trigger
        self._inflight_attempts = {}
        self._healer_handle = None
        #: health wiring (ISSUE 5): handle of the child-stack provider this
        #: pool registered, plus the exact monitor/scope it was registered ON
        #: (handles are per-monitor sequence numbers — removing with a handle
        #: issued by a DIFFERENT monitor could delete an unrelated provider)
        self._stack_provider_handle = None
        self._stack_provider_monitor = None
        #: idle children ping the control pipe at this cadence so a live-but-
        #: unemployed child is distinguishable from a dead one in the evidence
        #: (pings are drained by the driver before every result header)
        self._ping_interval_s = float(
            os.environ.get("PTPU_CHILD_PING_S", "") or 5.0)
        #: live cross-process knob actuation (ISSUE 14 satellite): pending
        #: control-frame payload + a version stamp per driver; drivers send
        #: the frame on the pool wire (beside the slab-grant protocol) before
        #: their next item dispatch, children apply and ack. All under
        #: _ctl_lock — broadcast_io_knobs() is called from the controller
        #: thread while drivers read concurrently.
        self._ctl_lock = threading.Lock()
        self._ctl_pending = {}
        self._ctl_version = 0
        self._ctl_seen = {}   # driver idx -> version last sent to its child
        self._ctl_acks = {}   # driver idx -> {knob: applied value}

    def start(self, worker, plan):
        import os
        import tempfile

        self._results = queue.Queue(maxsize=self._queue_size)
        self._stop_event.clear()
        self.truncated = False
        authkey = self._authkey = os.urandom(32)
        if self._transport_name == "tcp":
            self._setup_hub(authkey)  # degrades self._transport_name on failure
        self._setup_shm()
        with self._respawn_lock:
            self._tmpdir = tempfile.mkdtemp(prefix="ptpu-pool-")
        # children must find petastorm_tpu BEFORE the bootstrap handshake can hand them
        # the parent's sys.path — put the package root on PYTHONPATH explicitly (the
        # parent may have found it via sys.path.insert, which does not propagate)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        child_pp = os.environ.get("PYTHONPATH", "")
        child_pp = pkg_root + ((os.pathsep + child_pp) if child_pp else "")
        self._worker = worker  # respawned replacements re-handshake the same worker
        # children never take an accelerator: the chip belongs to the parent,
        # and a second process that reaches for it fails or hangs
        self._child_env = {**os.environ, "PYTHONPATH": child_pp,
                           "JAX_PLATFORMS": "cpu"}
        # host-wide cache arena handoff (ISSUE 17): children attach the
        # parent's mapped warm set at bootstrap. On _child_env — the SAME env
        # every respawn/resize spawn reuses (_popen_child) — so a replacement
        # child's first read of a warm piece is served from the arena, not a
        # cold store refill (the respawned-child cold-start satellite).
        from petastorm_tpu.io import arena as _arena_mod

        arena_token = _arena_mod.current_token()
        if arena_token is not None:
            self._child_env[_arena_mod.ENV_ATTACH] = arena_token
        # tenant propagation (ISSUE 18): the reader stamps its resolved
        # context on the worker; children adopt it as their process default
        # via attach_from_env at bootstrap — the SAME env every respawn/resize
        # spawn reuses, so replacements keep billing the right tenant
        tenant_ctx = getattr(worker, "tenant_context", None)
        if tenant_ctx is None:
            from petastorm_tpu.obs import tenant as _tenant_mod

            tenant_ctx = _tenant_mod.current()
        if tenant_ctx is not None:
            self._child_env.update(tenant_ctx.env())
            self._tenant_label = tenant_ctx.tenant
        else:
            self._tenant_label = None
        if self._transport_name == "tcp":
            # the child's link policy (redial backoff, heartbeat cadence,
            # half-open threshold) rides the environment: the transport must
            # bootstrap BEFORE any handshake payload could carry it
            rec = self._recovery
            self._child_env.update({
                "PTPU_LINK_HEARTBEAT_S": repr(rec.link_heartbeat_s),
                "PTPU_LINK_MISS_THRESHOLD": str(rec.link_miss_threshold),
                "PTPU_LINK_RECONNECT_S": repr(rec.link_reconnect_s),
                "PTPU_LINK_CONNECT_TIMEOUT_S":
                    repr(rec.link_connect_timeout_s),
                "PTPU_IO_RETRY_BACKOFF_S": repr(rec.io_retry_backoff_s),
                "PTPU_IO_RETRY_MAX_BACKOFF_S":
                    repr(rec.io_retry_max_backoff_s),
            })
            self._start_children_tcp(authkey)
        else:
            self._start_children_pipe(authkey)
        monitor = self._health
        self._dispatch = PullDispatcher(
            plan, self._workers_count, lookahead=self._lookahead,
            stealing=self._stealing,
            recorder=monitor.flight if monitor is not None else None)
        with self._active_lock:
            self._active = self._workers_count
            self._target = self._workers_count
            self._retire = 0
            self._next_idx = self._workers_count
        for i, conn in enumerate(self._conns):
            t = threading.Thread(target=self._drive_child,
                                 args=(conn, self._dispatch, i),
                                 daemon=True, name="ptpu-pdrv-%d" % i)
            t.start()
            self._threads.append(t)

    def _setup_hub(self, authkey):
        """Create the tcp listener hub (ISSUE 15). A setup failure — cannot
        bind/listen — is a CLASSIFIED degradation back to the local pipe
        pool, never a raise: the transport is an availability feature and
        must not cost any."""
        from petastorm_tpu.transport.tcp import TcpHub

        try:
            hub = TcpHub(self._recovery, token=authkey.hex())
        except Exception as e:  # noqa: BLE001 — degrade, never fail the pool
            from petastorm_tpu.obs.log import degradation

            degradation(
                "transport_link_down",
                "tcp transport unavailable (%s); falling back to the local "
                "pipe pool", e, once=False)
            hub = None
            self._transport_name = "pipe"
        with self._respawn_lock:  # join() hands the hub off under this lock
            self._hub = hub

    def _start_children_pipe(self, authkey):
        """Spawn + handshake the initial fleet over the unix-socket pipe wire
        (today's behavior, byte-identical)."""
        import os
        from multiprocessing.connection import Listener

        from petastorm_tpu.transport import PipeTransport

        with self._respawn_lock:
            address = os.path.join(self._tmpdir, "sock")
        # every child dials at once: a backlog of one refuses all but the first
        # with EAGAIN where the kernel does not queue the rest
        listener = Listener(address, family="AF_UNIX", authkey=authkey,
                            backlog=max(8, self._workers_count))
        for _ in range(self._workers_count):
            child = self._popen_child(address, authkey)
            with self._respawn_lock:  # _spawn_one/join also touch the proc list
                self._procs.append(child)
        # accept on a helper thread + child liveness poll on this one: a child that dies
        # before connecting (import error, crash) must raise here, not hang Reader
        # construction forever. Public API only — no reaching into Listener internals
        # for socket timeouts (ADVICE r1: private attrs break across Python versions
        # and made every OSError look like a poll tick).
        accepted = queue.Queue()

        def _accept_loop():
            try:
                for _ in range(self._workers_count):
                    accepted.put(listener.accept())
            except Exception as e:  # noqa: BLE001 — surfaced to the main thread
                accepted.put(e)

        acceptor = threading.Thread(target=_accept_loop, name="ptpu-accept", daemon=True)
        acceptor.start()
        try:
            # two phases so children bootstrap CONCURRENTLY: send every
            # handshake payload as its connection arrives, then collect the
            # pid acks — awaiting each ack inline would serialize the pool's
            # startup behind every child's full import + worker unpickle
            # (sum of bootstraps instead of the slowest one)
            pending = []
            while len(pending) < self._workers_count:
                conn = PipeTransport(
                    self._await_accept(accepted, self._procs, "Pool child"))
                self._send_handshake(conn)
                pending.append(conn)
            for conn in pending:
                self._register_conn(conn)
        finally:
            listener.close()  # also unblocks the acceptor thread if we raised

    def _start_children_tcp(self, authkey):
        """Spawn + handshake the initial fleet over the framed tcp transport:
        one hub session per child, children dial back concurrently."""
        pending = []
        for _ in range(self._workers_count):
            with self._respawn_lock:
                sid = self._session_counter
                self._session_counter += 1
            transport = self._hub.create_session(sid)
            if self._tenant_label is not None:
                transport.set_tenant(self._tenant_label)
            child = self._popen_child(self._hub.address_for(sid), authkey)
            with self._respawn_lock:
                self._procs.append(child)
            pending.append(transport)
        # same two-phase shape as the pipe path: handshake each link as it
        # connects, then collect the pid acks
        for transport in pending:
            self._await_tcp_connected(transport, "Pool child")
            self._send_handshake(transport)
        for transport in pending:
            self._register_conn(transport)

    def _register_conn(self, conn):
        """Collect one child's pid ack and register its connection as the
        next driver slot. Accept order ≠ spawn order: the handshake's pid ack
        is what ties this connection (→ driver idx) to its OS process — the
        heal tier kills by exactly this mapping."""
        pid = self._await_pid_ack(conn)
        conn.mark_ready()  # steady-state link: chaos sites + heartbeats on
        with self._respawn_lock:
            idx = len(self._conns)
            self._conns.append(conn)
            for p in self._procs:
                if p.pid == pid:
                    self._child_by_idx[idx] = p
                    break

    def _await_tcp_connected(self, transport, what, procs=None,
                             check_stop=False, deadline=120.0):
        """Bounded wait for one tcp session's first adoption, polling child
        liveness every second — the tcp twin of :meth:`_await_accept` (same
        tolerance: a host slow enough to need start()'s full window must also
        be able to heal)."""
        waited = 0.0
        while not transport.wait_connected(1.0):
            waited += 1.0
            if check_stop and self._stop_event.is_set():
                raise RuntimeError("pool stopping during respawn")
            with self._respawn_lock:
                snapshot = list(self._procs if procs is None else procs)
            for p in snapshot:
                if p.poll() is not None:
                    raise RuntimeError(
                        "%s exited with code %s before connecting (run 'python "
                        "-m petastorm_tpu._child_worker' manually to debug)"
                        % (what, p.returncode))
            if waited > deadline:
                raise TimeoutWaitingForResultError(
                    "%s did not connect within %.0fs" % (what, deadline))

    def _should_retire(self):
        """Claim one pending retirement (live shrink): checked by drivers
        BETWEEN items only — a shrink drains, it never kills mid-item."""
        with self._active_lock:
            if self._retire > 0:
                self._retire -= 1
                return True
            return False

    def set_lookahead(self, lookahead):
        """Live dispatch-lookahead retune (parent side; a child's own
        readahead pool follows the hints it is sent)."""
        self._lookahead = max(0, int(lookahead))
        dispatch = self._dispatch
        if dispatch is not None:
            dispatch.set_lookahead(self._lookahead)

    @property
    def alive_workers(self):
        """Lock-free like ThreadExecutor.alive_workers (collector-safe)."""
        return self._active

    @property
    def target_workers(self):
        return self._target

    def resize(self, workers_count):
        """Grow/shrink the child fleet LIVE (ISSUE 13). Grow spawns clean
        interpreter children through the same handshake as the initial pool
        (and the elastic respawn path); shrink queues retirements — a
        retiring driver finishes its in-flight item, returns its unprocessed
        claim to the dispatcher, sends the orderly-shutdown ``None`` to its
        child and drains out. Never kills mid-item; the delivered ∪
        quarantined set is identical to an un-resized run. Returns the
        applied target (spawn failures leave the fleet smaller and are
        degradation-logged)."""
        n = max(1, int(workers_count))
        dispatch = self._dispatch
        grow_idxs = []
        with self._active_lock:
            if dispatch is None or self._active == 0:
                return self._target
            if n > self._target:
                grow = n - self._target
                cancelled = min(grow, self._retire)
                self._retire -= cancelled
                for _ in range(grow - cancelled):
                    grow_idxs.append(self._next_idx)
                    self._next_idx += 1
            elif n < self._target:
                self._retire += self._target - n
            self._target = n
            next_idx = self._next_idx
        if not grow_idxs:
            return n
        dispatch.ensure_workers(next_idx)
        from petastorm_tpu.obs.log import degradation

        # the slots were RESERVED in _active above (before the slow child
        # spawns): concurrent driver exits must not see a transient zero and
        # post _DONE while a grown child is mid-handshake
        with self._active_lock:
            self._active += len(grow_idxs)
        for idx in grow_idxs:
            try:
                conn, proc = self._spawn_one()
            except Exception as e:  # noqa: BLE001 — degrade, never fail the pool
                degradation(
                    "ctl_spawn_failed",
                    "live fleet grow could not spawn a pool child (%s); "
                    "running with %d fewer worker(s) than the target", e,
                    1, once=False)
                with self._active_lock:
                    self._target -= 1
                    self._active -= 1
                    last = self._active == 0
                if last:
                    self._put(_DONE)  # the reservation was the only holdout
                continue
            with self._respawn_lock:
                self._child_by_idx[idx] = proc
            t = threading.Thread(target=self._drive_child,
                                 args=(conn, dispatch, idx),
                                 daemon=True, name="ptpu-pdrv-%d" % idx)
            t.start()
            self._threads.append(t)
        with self._active_lock:
            return self._target

    def _await_accept(self, accepted, procs, what, check_stop=False, deadline=120.0):
        """Wait for one accepted connection (or the acceptor thread's exception),
        polling child liveness every second — ONE copy of the accept protocol shared
        by the initial pool spawn and elastic respawns (same tolerance both places: a
        host slow enough to need start()'s full window must also be able to heal)."""
        waited = 0.0
        while True:
            try:
                item = accepted.get(timeout=1.0)
                break
            except queue.Empty:
                waited += 1.0
                if check_stop and self._stop_event.is_set():
                    raise RuntimeError("pool stopping during respawn")
                for p in procs:
                    if p.poll() is not None:
                        raise RuntimeError(
                            "%s exited with code %s before connecting (run 'python "
                            "-m petastorm_tpu._child_worker' manually to debug)"
                            % (what, p.returncode))
                if waited > deadline:
                    raise TimeoutWaitingForResultError(
                        "%s did not connect within %.0fs" % (what, deadline))
        if isinstance(item, Exception):
            raise item
        return item

    def _popen_child(self, address, authkey):
        """Launch one clean-interpreter child pointed at ``address`` (shared by the
        initial pool spawn and elastic respawns — ONE copy of the protocol)."""
        import subprocess
        import sys

        p = subprocess.Popen(
            [sys.executable, "-m", "petastorm_tpu._child_worker", address],
            stdin=subprocess.PIPE, env=self._child_env,
        )
        p.stdin.write(authkey)
        p.stdin.close()
        return p

    def _setup_shm(self):
        """Create the slab ring when an shm-family serializer was requested.

        Graceful degradation is part of the contract: a platform without working
        shared memory (or a ring-creation failure, e.g. a tiny ``/dev/shm``)
        swaps the pool down to the inner socket serializer with a warn-once and a
        ``wire_stats()['shm_unavailable']`` marker — same results, socket copies.
        """
        from petastorm_tpu.serializers import ShmSerializer

        if not isinstance(self._serializer, ShmSerializer):
            return
        if self._transport_name == "tcp":
            # the tcp wire must behave as if the host boundary were real
            # (ROADMAP item 1: the same frames cross hosts tomorrow) — slab
            # grants cannot ride a network link, so payloads take the socket
            # frames. Classified, warn-once, and visible in wire_stats().
            from petastorm_tpu.obs.log import degradation

            degradation(
                "transport_shm_bypass",
                "shared-memory slab wire disabled over the tcp transport; "
                "result payloads ride the framed socket wire instead")
            self._shm_unavailable = True
            self._serializer_name = self._serializer.inner_name
            self._serializer = self._serializer.inner
            return
        from petastorm_tpu.parallel.shm_ring import SlabRing, shm_supported

        ring = None
        if shm_supported():
            try:
                ring = SlabRing(self._shm_slab_bytes,
                                self._shm_slabs or (self._workers_count + 2),
                                trace=self._tracer)
            except Exception as e:  # noqa: BLE001 — degrade, never fail the pool
                from petastorm_tpu.obs.log import degradation

                degradation("shm_ring_create_failed",
                            "shared-memory slab ring creation failed (%s); "
                            "falling back to the socket wire", e, once=False)
        if ring is None:
            self._shm_unavailable = True
            self._serializer_name = self._serializer.inner_name
            self._serializer = self._serializer.inner
            return
        self._serializer.bind_ring(ring)
        with self._respawn_lock:  # join() takes the ring under the same lock
            self._ring = ring

    def set_trace(self, tracer):
        """Attach a :class:`petastorm_tpu.trace.TraceRecorder`: the slab ring
        records ``shm.acquire_wait`` spans (driver threads starved for a slab)."""
        self._tracer = tracer
        if self._ring is not None:
            self._ring.set_trace(tracer)

    def set_health(self, monitor):
        """Attach a health monitor; additionally registers this pool's
        child-stack provider — on a stall the watchdog signals every live
        child (SIGUSR1 → faulthandler, see ``_child_worker.py``) and folds
        their thread stacks into the flight record."""
        super().set_health(monitor)
        if monitor is self._stack_provider_monitor:
            return
        # re-attach/detach: move the provider to the new monitor — the old
        # one must stop signaling this pool's children, and the handle is
        # only meaningful to the monitor that issued it
        old, self._stack_provider_monitor = self._stack_provider_monitor, None
        handle, self._stack_provider_handle = self._stack_provider_handle, None
        healer, self._healer_handle = self._healer_handle, None
        if old is not None:
            if handle is not None:
                old.remove_stack_provider(handle)
            if healer is not None:
                old.remove_healer(healer)
        if monitor is not None:
            self._stack_provider_handle = monitor.add_stack_provider(
                self._dump_child_stacks)
            # heal tier (ISSUE 7, escalation="heal"): on a stalled child actor
            # the watchdog asks this pool to kill the hung child — the driver's
            # dead-child machinery then respawns it and re-dispatches the item
            self._healer_handle = monitor.add_healer(self._heal_stalled)
            self._stack_provider_monitor = monitor

    def _dump_child_stacks(self):
        """Signal live children to faulthandler-dump their stacks and collect
        the files (the stall watchdog's cross-process evidence hook). Best
        effort: a child that cannot answer within ~2s is reported as such —
        which is itself evidence (SIGKILL'd? wedged in native code?)."""
        import os
        import signal
        import time

        if not hasattr(signal, "SIGUSR1"):
            return {}  # non-POSIX: driver stacks only
        with self._respawn_lock:
            procs = list(self._procs)
            tmpdir = self._tmpdir
        if not procs or not tmpdir:
            return {}
        # faulthandler APPENDS to the child's open dump file, so a second
        # stall must return only the bytes written AFTER this signal — a
        # previous capture's stack would send the operator to the WRONG hang
        def _size(pid):
            try:
                return os.path.getsize(
                    os.path.join(tmpdir, "stacks-%d.txt" % pid))
            except OSError:
                return 0

        alive = []
        offsets = {}
        for p in procs:
            if p.poll() is None:
                offsets[p.pid] = _size(p.pid)
                try:
                    p.send_signal(signal.SIGUSR1)
                    alive.append(p)
                except OSError:
                    pass
        out = {}
        pending = {p.pid for p in alive}
        partial = {}  # pid -> last read: accept only once the dump stops growing
        deadline = time.monotonic() + 2.0
        while pending and time.monotonic() < deadline:
            time.sleep(0.05)
            for pid in list(pending):
                try:
                    with open(os.path.join(tmpdir, "stacks-%d.txt" % pid)) as f:
                        f.seek(offsets[pid])
                        text = f.read()
                except OSError:
                    continue
                if not text.strip():
                    continue
                # faulthandler may still be mid-write (a child has several
                # threads): accepting the first non-empty read could cut the
                # dump off BEFORE the hung thread's frames — require one
                # stable re-read before shipping it as evidence
                if partial.get(pid) == text:
                    out["child-%d" % pid] = text
                    pending.discard(pid)
                else:
                    partial[pid] = text
        for pid in pending:
            # still growing (or silent) at the deadline: partial evidence
            # beats none, marked so the operator knows it may be cut off
            out["child-%d" % pid] = (
                partial[pid] + "\n<truncated: dump still growing at 2s>"
                if pid in partial else "<no faulthandler dump within 2s>")
        return out

    def _heal_stalled(self, stalled):
        """The ``escalation="heal"`` hook (ISSUE 7): kill the hung pool child
        behind each stalled ``worker.child-<idx>`` actor so the driver's
        dead-child machinery takes over — respawn against the budget, slab
        reclaim (lease-aware), and re-dispatch of the unfinished claim (or a
        quarantine skip once the item hits the poison threshold). Returns the
        actor names it acted on; actors it could NOT absorb (budget exhausted
        and the poison policy cannot eat the kill either) are left for the
        watchdog to escalate to :class:`StallError`.

        Called from the watchdog thread. Matching is by the FULL actor name
        this pool registered (scope-prefixed when the monitor is shared via a
        ``HealthScope``) — a suffix-only match would let one pool's healer
        kill ANOTHER pool's healthy child on a shared monitor, mask the real
        hang (the reported-stall debounce never re-arms for a child that
        never beats again), and burn a respawn for nothing."""
        import re

        healed = set()
        if self._stop_event.is_set():
            return healed
        from petastorm_tpu.obs.log import degradation

        for s in stalled:
            actor = s.get("actor", "")
            m = re.search(r"(?:^|/)worker\.child-(\d+)$", actor)
            if m is None:
                continue
            idx = int(m.group(1))
            if actor != self._child_actor_name(idx):
                continue  # a sibling pipeline's child on a shared monitor
            with self._respawn_lock:
                proc = self._child_by_idx.get(idx)
                budget = self._respawn_budget
                attempts = self._inflight_attempts.get(idx, 0)
            if proc is None or proc.poll() is not None:
                continue  # already dead: the driver is mid-respawn on its own
            # can the kill be absorbed? either the respawn budget pays for a
            # re-dispatch, or the poison policy quarantines the item (its
            # respawn is uncharged). If neither, do NOT pull the trigger —
            # killing would just turn the stall into WorkerDiedError; leaving
            # it lets the watchdog deliver StallError with the hang evidence.
            absorbable = budget > 0 or (
                self._recovery.quarantine
                and attempts + 1 >= self._recovery.poison_attempts)
            if not absorbable:
                continue
            try:
                proc.kill()
            except OSError:
                continue
            degradation(
                "stall_heal_kill",
                "Heal tier killed hung pool child pid=%s (actor %s, %.1fs past "
                "threshold); its item will be re-dispatched or quarantined",
                proc.pid, s["actor"], s.get("age_s", 0.0), once=False)
            healed.add(s["actor"])
        return healed

    def _child_actor_name(self, idx):
        """The full (scope-prefixed) actor name this pool's ``idx``-th child
        heartbeats under — exactly what ``_drive_child`` registers."""
        base = "worker.child-%d" % idx
        namer = getattr(self._health, "_name", None)
        return namer(base) if namer is not None else base

    def wire_stats(self):
        """Wire-transport gauges (shm slab occupancy/bytes/fallbacks/wait), or a
        degradation marker, or {} for plain socket serializers."""
        if self._ring is not None:
            return self._ring.stats()
        if self._shm_unavailable:
            return {"shm_unavailable": 1}
        return {}

    def dispatch_stats(self):
        """Work-stealing gauges for ``Reader.io_stats()`` (parent-side; the
        children's readahead counters live in their own processes)."""
        dispatch = self._dispatch
        return dispatch.stats() if dispatch is not None else {}

    @property
    def wire_views(self):
        """True when deserialized payloads are zero-copy READ-ONLY slab views
        (shm view mode) — consumers that buffer rows must detach them first."""
        from petastorm_tpu.serializers import ShmSerializer

        return (isinstance(self._serializer, ShmSerializer)
                and not self._serializer.writable)

    def _send_handshake(self, conn):
        """Bootstrap a connected child: parent sys.path, wire serializer (plus
        the slab-ring attach config in shm mode), health config, worker.

        The health slot is ALWAYS sent (ISSUE 5): the stack-dump hook costs
        nothing until signaled and the idle ping rides the existing control
        pipe, so child-side evidence capture works even when the monitor is
        attached after the pool started (the driver drains ping messages
        unconditionally — see ``_recv_result``)."""
        import sys

        conn.send(list(sys.path))
        conn.send(self._serializer_name)
        if self._ring is not None:
            conn.send((self._ring.names, self._ring.slab_bytes))
        with self._respawn_lock:
            dump_dir = self._tmpdir
        conn.send({"stack_dump_dir": dump_dir,
                   "ping_interval_s": self._ping_interval_s})
        conn.send(self._worker)

    def _await_pid_ack(self, conn):
        """Collect the child's ``("pid", pid)`` ack (sent right after it
        unpickles the worker): ties the connection to its OS process (accept
        order is not spawn order) — the heal tier and dead-child bookkeeping
        key on it. Bounded: _await_accept already proved the process is alive
        and connected."""
        deadline = 120.0
        waited = 0.0
        while not conn.poll(1.0):
            waited += 1.0
            if waited > deadline:
                raise TimeoutWaitingForResultError(
                    "pool child connected but never sent its pid ack "
                    "(worker unpickle wedged?)")
        ack = conn.recv()
        if not (isinstance(ack, tuple) and len(ack) == 2 and ack[0] == "pid"):
            raise RuntimeError("unexpected pool-child handshake ack %r" % (ack,))
        return ack[1]

    def _handshake(self, conn):
        """Send + collect in one call (the single-child respawn path)."""
        self._send_handshake(conn)
        return self._await_pid_ack(conn)

    def _spawn_one(self):
        """Spawn + handshake ONE replacement child (elastic respawn / live
        grow / strand rescue). Returns ``(connection, process)``; raises when
        the child cannot start/connect or the pool is stopping (the
        replacement is then killed, never leaked). On the tcp transport a
        spawn whose LINK cannot establish falls back to a pipe-connected
        local child — all-links-down degrades to the local pool as a
        classified degradation, never a hang or a hard failure."""
        if self._transport_name == "tcp" and self._hub is not None:
            try:
                return self._spawn_one_tcp()
            except Exception as e:  # noqa: BLE001 — degrade to the local pool
                if self._stop_event.is_set():
                    raise
                from petastorm_tpu.obs.log import degradation

                degradation(
                    "transport_link_down",
                    "tcp child spawn could not establish a link (%s); "
                    "falling back to a pipe-connected local child", e,
                    once=False)
        return self._spawn_one_pipe()

    def _spawn_one_tcp(self):
        """One replacement child over a fresh tcp hub session."""
        with self._respawn_lock:
            if self._tmpdir is None:
                raise RuntimeError("pool stopping during respawn")
            sid = self._session_counter
            self._session_counter += 1
        transport = self._hub.create_session(sid)
        if getattr(self, "_tenant_label", None) is not None:
            transport.set_tenant(self._tenant_label)
        p = None
        try:
            p = self._popen_child(self._hub.address_for(sid), self._authkey)
            self._await_tcp_connected(transport, "respawned pool child",
                                      procs=[p], check_stop=True)
            self._send_handshake(transport)
            self._await_pid_ack(transport)
            transport.mark_ready()
            with self._respawn_lock:
                if self._stop_event.is_set():
                    raise RuntimeError("pool stopping during respawn")
                self._procs.append(p)
                self._conns.append(transport)
            return transport, p
        except BaseException:
            self._hub.drop_session(sid)
            transport.close()
            if p is not None:
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass  # graftlint: disable=GL-O002 (best-effort kill on the raising path)
            raise

    def _spawn_one_pipe(self):
        """One replacement child over the unix-socket pipe wire."""
        import os
        from multiprocessing.connection import Listener

        with self._respawn_lock:
            self._spawn_counter += 1
            address = os.path.join(self._tmpdir, "sock-r%d" % self._spawn_counter)
        authkey = os.urandom(32)
        listener = Listener(address, family="AF_UNIX", authkey=authkey)
        p = None
        conn = None
        try:
            p = self._popen_child(address, authkey)
            accepted = queue.Queue()

            def _accept():
                try:
                    accepted.put(listener.accept())
                except Exception as e:  # noqa: BLE001 — surfaced below
                    accepted.put(e)

            t = threading.Thread(target=_accept, daemon=True, name="ptpu-respawn-accept")
            t.start()
            from petastorm_tpu.transport import PipeTransport

            conn = PipeTransport(
                self._await_accept(accepted, [p], "respawned pool child",
                                   check_stop=True))
            self._handshake(conn)
            conn.mark_ready()
            with self._respawn_lock:
                # join()/stop() may have begun while we were mid-handshake:
                # registering into already-cleared lists would leak an unreaped
                # child and an open socket (join() holds this lock to clear them)
                if self._stop_event.is_set():
                    raise RuntimeError("pool stopping during respawn")
                self._procs.append(p)
                self._conns.append(conn)
            return conn, p
        except BaseException:
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass
            if p is not None:
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass  # graftlint: disable=GL-O002 (best-effort kill on the raising path)
            raise
        finally:
            listener.close()

    def _respawn(self, err, idx, charged=True):
        """A replacement connection for a dead child (registered as driver
        ``idx``'s child), or None when the budget is exhausted / the pool is
        stopping / the spawn itself fails.

        ``charged=False`` is the quarantine path (ISSUE 7): the dead child's
        item reached the poison threshold and will be SKIPPED, so the respawn
        only restores pool capacity — it cannot crash-loop, and charging it
        would let one poison item eat the whole budget."""
        with self._respawn_lock:
            if self._stop_event.is_set():
                return None
            if charged:
                if self._respawn_budget <= 0:
                    return None
                self._respawn_budget -= 1
            budget_left = self._respawn_budget
        from petastorm_tpu.obs.log import degradation

        try:
            conn, proc = self._spawn_one()
        except Exception as e:  # noqa: BLE001 — degrade to the fatal path
            degradation("respawn_failed", "Pool child respawn failed: %s", e,
                        once=False)
            return None
        with self._respawn_lock:
            self._child_by_idx[idx] = proc
        degradation(
            "worker_died",
            "Pool worker died (%s); respawned a replacement and %s its "
            "item (remaining respawn budget: %d)", err,
            "re-dispatching" if charged else "quarantining", budget_left,
            once=False)
        return conn

    def _recv_result(self, conn, child_hb, idx=None):
        """Receive the next result/exc header, draining child heartbeat pings
        (``("hb", ts)`` — sent at item receipt and while idle) into the
        child's heartbeat stamp, and control-frame acks (``("ctl_ack",
        applied)``) into the pool's ack ledger. Children always ping; without
        a monitor the pings are simply dropped here (one tuple check per
        message).

        The receive is a bounded poll loop, not a bare ``recv()`` (GL-R001):
        once the pool is stopping this driver abandons the wait promptly —
        a child hung in native code used to pin its driver in ``recv`` for
        the full 10s thread-join timeout on every teardown."""
        if _chaos.ACTIVE is not None:
            _chaos.ACTIVE.hit("pool.recv")
        while True:
            while not conn.poll(0.2):
                if self._stop_event.is_set():
                    raise EOFError("pool stopping while awaiting a child result")
            msg = conn.recv()  # graftlint: disable=GL-R001 (poll above bounds it)
            if isinstance(msg, tuple) and len(msg) == 2 and msg[0] == "hb":
                if child_hb is not None:
                    child_hb.beat("working")
                continue
            if isinstance(msg, tuple) and len(msg) == 2 and msg[0] == "ctl_ack":
                if idx is not None:
                    with self._ctl_lock:
                        self._ctl_acks.setdefault(idx, {}).update(msg[1] or {})
                continue
            return msg

    # -- live cross-process knobs (ISSUE 14 satellite) ----------------------------------

    def broadcast_io_knobs(self, knobs):
        """Queue a ``{knob: value}`` retune for every RUNNING child: each
        driver sends one ``("ctl", knobs)`` frame on its item pipe before its
        next dispatch (beside the slab-grant protocol), the child applies via
        its worker's ``apply_<knob>()`` seam and acks. Children spawned AFTER
        the retune inherit it through the worker pickle instead (the PR 13
        behavior, now the backstop rather than the only path)."""
        if not knobs:
            return
        with self._ctl_lock:
            self._ctl_pending.update(knobs)
            self._ctl_version += 1

    def _pending_ctl(self, idx):
        """The control frame driver ``idx`` still owes its child, or None."""
        with self._ctl_lock:
            if not self._ctl_pending \
                    or self._ctl_seen.get(idx, 0) == self._ctl_version:
                return None
            self._ctl_seen[idx] = self._ctl_version
            return dict(self._ctl_pending)

    def ctl_acks(self):
        """``{driver idx: {knob: applied value}}`` — which children confirmed
        a live retune (the autotune harness asserts a child-side retune lands
        WITHOUT a respawn)."""
        with self._ctl_lock:
            return {idx: dict(acks) for idx, acks in self._ctl_acks.items()}

    def _drive_child(self, conn, dispatch, idx):
        import time

        from petastorm_tpu.serializers import KIND_SHM

        # local snapshot: join() nulls self._ring (under the respawn lock) while a
        # straggling driver may still be mid-item past its 10s join timeout — the
        # ring object itself stays safe to call (close() makes release a no-op)
        ring = self._ring
        hb = None        # this driver thread's heartbeat (all wait states)
        child_hb = None  # the child's: stamped from pipe traffic, watchdogged
        try:
            fatal = False
            while not fatal and not self._stop_event.is_set():
                if self._should_retire():
                    # live shrink (ISSUE 13): hand the unprocessed claim back
                    # and drain out — the post-loop None send retires the
                    # child cleanly; its process is reaped by join()
                    dispatch.withdraw(idx)
                    with self._respawn_lock:
                        self._child_by_idx.pop(idx, None)
                    break
                monitor = self._health
                if monitor is not None and hb is None:
                    hb = monitor.register("pooldrv-%d" % idx, "worker")
                    child_hb = monitor.register("worker.child-%d" % idx, "child")
                if hb is not None:
                    hb.wait("claim")
                claim = dispatch.next(idx)
                if claim is None:
                    break
                item, upcoming = claim
                # readahead hint rides with the item: the child prefetches these
                # on ITS IO pool before working the item (they are this driver's
                # claimed pieces, so barring a steal the child reads its own future)
                hints = list(upcoming)
                prov = self._prov  # resolved per item, attachable mid-stream
                prov_id = _prov.item_identity(item) if prov is not None \
                    else None
                recovery = self._recovery
                attempts = 0       # failures of THIS item, across respawns/heals
                first_death = None  # the ORIGINAL child failure (ISSUE 7: budget
                #                     exhaustion must surface it, not a wrapper)
                self._inflight_attempts[idx] = 0
                while True:  # item attempts: survives child death via respawn
                    # slab grant per ATTEMPT: a respawned child gets a fresh grant,
                    # and a dead child's in-flight slab is reclaimed below
                    slab = None
                    if ring is not None:
                        t_slab = time.perf_counter() if prov is not None else 0.0
                        slab = ring.acquire()
                        if prov is not None:
                            prov.add_item_span(prov_id[0], prov_id[1],
                                               "wire.slab_wait", t_slab,
                                               time.perf_counter(),
                                               key=prov_id[2])
                        if slab is None:  # ring starved: socket wire for this item
                            ring.count_fallback()
                    try:
                        if child_hb is not None:
                            child_hb.beat("working")
                        if hb is not None:
                            # the driver is only WAITING here; the hang
                            # candidate is the child, and ITS heartbeat (stamped
                            # at send, from pings, and at the header) carries
                            # the stall detection
                            hb.wait("child")
                        t0 = time.perf_counter() if monitor is not None else 0.0
                        if _chaos.ACTIVE is not None:
                            _chaos.ACTIVE.hit("pool.dispatch",
                                              key=_chaos.item_key(item))
                        ctl = self._pending_ctl(idx)
                        if ctl is not None:
                            # live knob control frame (ISSUE 14 satellite):
                            # the retune rides the item pipe ahead of the
                            # next dispatch — the child applies + acks, no
                            # respawn involved
                            conn.send(("ctl", ctl))
                        t_send = time.perf_counter() if prov is not None else 0.0
                        # in-flight ledger (ISSUE 15): the item is tracked on
                        # its link until the result conversation completes —
                        # whatever is still tracked at a link death is exactly
                        # what re-dispatches (no-op on the pipe transport)
                        conn.track(item)
                        conn.send((slab, item, hints) if ring is not None
                                  else (item, hints))
                        header = self._recv_result(conn, child_hb, idx=idx)
                        if prov is not None:
                            # the child's own spans nest INSIDE this roundtrip
                            # once merged — the flame fold charges the wire the
                            # residual, not the child's work
                            prov.add_item_span(prov_id[0], prov_id[1],
                                               "wire.roundtrip", t_send,
                                               time.perf_counter(),
                                               key=prov_id[2])
                        if monitor is not None:
                            monitor.observe_worker(idx, time.perf_counter() - t0)
                        if child_hb is not None:
                            child_hb.wait("idle")
                        if header[0] == "exc":
                            conn.settle()  # the conversation completed
                            if slab is not None:
                                ring.release(slab)
                            attempts += 1
                            self._inflight_attempts[idx] = attempts
                            if not recovery.quarantine:
                                self._put(_ExcResult(header[1]))
                                fatal = True
                                break
                            if attempts >= recovery.poison_attempts:
                                # poison by exception: skip it, keep the pool
                                self._put(QuarantinedItem(item, header[1],
                                                          attempts))
                                break  # the child is alive: next item
                            continue  # retry on the same live child
                        _, kind, nframes, trace_blob = header
                        if trace_blob is not None:
                            # cross-process merge: the child's per-item spans,
                            # clock-aligned onto the parent recorder's timeline.
                            # Slot 5 (when present) is the provenance piggyback
                            # (ISSUE 10) riding the same anchors.
                            child_pid, wall0, perf0, spans = trace_blob[:4]
                            if self._tracer is not None:
                                self._tracer.add_child(child_pid, spans,
                                                       wall0, perf0)
                            if prov is not None and len(trace_blob) > 4 \
                                    and trace_blob[4] is not None:
                                prov.absorb_child(trace_blob[4], child_pid,
                                                  wall0, perf0)
                        frames = [conn.recv_bytes() for _ in range(nframes)]
                        conn.settle()  # result fully received off the link
                        if slab is not None and kind != KIND_SHM:
                            # granted but unused (oversized payload): reclaim first
                            # so a deserialize error cannot leak the slab
                            ring.release(slab)
                            ring.count_fallback()
                            slab = None
                        # kind == KIND_SHM transfers slab ownership to deserialize
                        # HERE (released there on its own failure, or leased to
                        # the consumer in view mode) — `slab` must be cleared
                        # BEFORE the call: a decode error below must never
                        # double-release a slab the lease contract already owns
                        # (the free list would hand one slab to two children,
                        # silently corrupting a consumer-retained batch). The
                        # one exception: a failure BEFORE deserialize could
                        # even parse the descriptor (slab_released=False on
                        # the exception) leaves the grant with this driver.
                        granted, slab = slab, None
                        t_dec = time.perf_counter() if prov is not None else 0.0
                        if _chaos.ACTIVE is not None:
                            frames = _chaos.ACTIVE.hit(
                                "wire.decode", key=_chaos.item_key(item),
                                payload=frames)
                        try:
                            result = self._serializer.deserialize(kind, frames)
                            if prov is not None:
                                # covers the chaos wire.decode injection site
                                # too, so an injected wire stall lands in this
                                # span's self time
                                prov.add_item_span(prov_id[0], prov_id[1],
                                                   "wire.decode", t_dec,
                                                   time.perf_counter(),
                                                   key=prov_id[2])
                        except Exception as e:  # noqa: BLE001 — policy-classified
                            if granted is not None and \
                                    not getattr(e, "slab_released", True):
                                ring.release(granted)
                            # wire-decode failure (corrupt bytes, truncated
                            # descriptor): the child is ALIVE and the pipe is
                            # intact. An EOFError out of pickle.loads used to
                            # masquerade as a child death here — blind slab
                            # release (double free) plus a pointless respawn of
                            # a live child. Classify it like a worker exception
                            # instead: poison policy applies, the item re-runs
                            # on the same child.
                            attempts += 1
                            self._inflight_attempts[idx] = attempts
                            if not recovery.quarantine:
                                self._put(_ExcResult(e))
                                fatal = True
                                break
                            if attempts >= recovery.poison_attempts:
                                self._put(QuarantinedItem(item, e, attempts,
                                                          kind="wire_decode"))
                                break
                            continue  # re-dispatch the item on the same child
                    except (EOFError, BrokenPipeError, ConnectionResetError) as e:
                        if slab is not None:
                            # dead child's in-flight slab: reclaim is lease-aware
                            # (revokes any outstanding consumer lease instead of
                            # re-inserting a still-leased slab into the free list)
                            ring.reclaim(slab)
                        if self._stop_event.is_set():
                            fatal = True  # teardown abandon, not a child failure
                            break
                        attempts += 1
                        self._inflight_attempts[idx] = attempts
                        if first_death is None:
                            first_death = e
                        # poison policy: an item that keeps killing children is
                        # skipped after poison_attempts; its respawn restores
                        # pool capacity WITHOUT charging the budget (the item
                        # will not be retried — no crash-loop risk)
                        poison = (recovery.quarantine
                                  and attempts >= recovery.poison_attempts)
                        # transport-level link death (ISSUE 15): when the
                        # child PROCESS is alive only the LINK died — the
                        # child redials with jittered backoff and the hub
                        # re-adopts; re-dispatch on the same child then. An
                        # attempt is charged (the poison policy applies — a
                        # frame that reliably kills its link quarantines like
                        # any poison item) but the respawn budget is not.
                        # Under on_poison='raise' the fast-path is BOUNDED by
                        # the poison threshold too: past it we fall through
                        # to the respawn path, whose budget (and then
                        # WorkerDiedError) bounds a deterministic link-killer
                        # exactly like the pipe wire's child-death contract —
                        # never an unbounded reconnect spin. PipeTransport
                        # has no reconnect: a dead pipe IS a dead child.
                        reconnect = getattr(conn, "reconnect", None)
                        if reconnect is not None \
                                and (recovery.quarantine
                                     or attempts < recovery.poison_attempts) \
                                and not self._stop_event.is_set():
                            with self._respawn_lock:
                                proc = self._child_by_idx.get(idx)
                            if proc is not None and proc.poll() is None \
                                    and reconnect():
                                with self._ctl_lock:
                                    # a knob frame may have died with the old
                                    # link: re-arm the pending-control send so
                                    # the retune rides the fresh one (applies
                                    # are idempotent)
                                    if self._ctl_pending:
                                        self._ctl_seen[idx] = 0
                                if poison:
                                    self._put(QuarantinedItem(
                                        item, e, attempts, kind="link_death"))
                                    break
                                continue  # re-dispatch on the healed link
                        replacement = self._respawn(e, idx, charged=not poison)
                        if poison:
                            self._put(QuarantinedItem(item, e, attempts,
                                                      kind="child_death"))
                        if replacement is None:
                            if not self._stop_event.is_set():
                                err = WorkerDiedError(
                                    "worker process died%s and no replacement "
                                    "could be spawned (respawn budget "
                                    "exhausted, or the spawn itself failed — "
                                    "see the respawn_failed degradation): %s"
                                    % (" %d time(s) on one item" % attempts
                                       if attempts > 1 else "", first_death),
                                    original=first_death)
                                self._put(_ExcResult(err))
                            fatal = True
                            break
                        try:
                            conn.close()
                        except OSError:
                            pass
                        if self._hub is not None \
                                and hasattr(conn, "session"):
                            # a zombie child redialing its DEAD session must
                            # find it gone, not adopt into a closed transport
                            self._hub.drop_session(conn.session)
                        conn = replacement
                        with self._ctl_lock:
                            # the fresh child inherited current knob overrides
                            # through the worker pickle — no frame owed
                            self._ctl_seen[idx] = self._ctl_version
                        if poison:
                            break  # quarantined: the fresh child takes the NEXT item
                        continue  # re-dispatch the SAME item on the fresh child
                    except Exception as e:  # noqa: BLE001 — a bad frame must surface,
                        self._put(_ExcResult(e))  # not silently truncate the dataset
                        fatal = True
                        break
                    if hb is not None:
                        hb.wait("results_put")  # full results queue = backpressure
                    self._put(result)
                    break
            try:
                conn.send(None)  # orderly shutdown
            except (BrokenPipeError, OSError):
                pass
        finally:
            if hb is not None:
                hb.done()
            if child_hb is not None:
                child_hb.done()
            self._retire_driver(dispatch, fatal)

    def _retire_driver(self, dispatch, fatal):
        """The drivers' exit gate — same strand-rescue contract as
        :meth:`ThreadExecutor._retire_worker`: the last decrementer finding
        handed-back claims in the dispatcher spawns a rescue child (this
        driver's own child already received its orderly shutdown) instead of
        posting ``_DONE`` over undelivered rows."""
        rescue_idx = None
        with self._active_lock:
            self._active -= 1
            if self._active == 0 and not fatal \
                    and not self._stop_event.is_set() and dispatch.has_work():
                self._active += 1
                rescue_idx = self._next_idx
                self._next_idx += 1
            last = self._active == 0
        if rescue_idx is not None:
            try:
                conn, proc = self._spawn_one()
                with self._respawn_lock:
                    self._child_by_idx[rescue_idx] = proc
                dispatch.ensure_workers(rescue_idx + 1)
                t = threading.Thread(target=self._drive_child,
                                     args=(conn, dispatch, rescue_idx),
                                     daemon=True,
                                     name="ptpu-pdrv-%d" % rescue_idx)
                t.start()
                self._threads.append(t)
                return
            except Exception as e:  # noqa: BLE001 — degrade to stream end
                from petastorm_tpu.obs.log import degradation

                degradation(
                    "ctl_rescue_failed",
                    "stranded-claim rescue child could not start (%s); the "
                    "handed-back items are LOST for this pass", e, once=False)
                with self._active_lock:
                    self._active -= 1
                    last = self._active == 0
        if last:
            # OUTSIDE the lock (see ThreadExecutor._retire_worker)
            self._put(_DONE)

    def _put(self, value):
        # Even the _DONE marker yields to a SET stop event: the consumer is the one
        # who sets it, and it never reads results afterwards — spinning until the
        # full queue drains would park the last worker for join()'s whole timeout
        # (results_timeout_s) on every stop-mid-stream teardown.
        while True:
            try:
                self._results.put(value, timeout=0.1)
                return
            except queue.Full:
                if self._stop_event.is_set():
                    return

    def results(self):
        return self._drain_results()

    def stop(self):
        self._stop_event.set()
        try:
            while True:
                self._results.get_nowait()
        except (queue.Empty, AttributeError):
            pass

    def join(self):
        import shutil

        # join == no more results wanted: setting the stop event aborts any in-flight
        # respawn within ~1s (otherwise a driver stuck in the 60s connect wait would
        # outlive the 10s thread join and register a child into cleared lists)
        self._stop_event.set()
        monitor = self._stack_provider_monitor
        self._stack_provider_monitor = None
        handle, self._stack_provider_handle = self._stack_provider_handle, None
        healer, self._healer_handle = self._healer_handle, None
        if monitor is not None:
            # a stall fired after this point must not signal (or heal-kill)
            # reaped children; removal goes to the monitor that ISSUED the handle
            if handle is not None:
                monitor.remove_stack_provider(handle)
            if healer is not None:
                monitor.remove_healer(healer)
        for t in self._threads:
            t.join(timeout=10)
        self._threads = []
        with self._respawn_lock:  # excludes a racing _spawn_one registration
            conns, self._conns = self._conns, []
            procs, self._procs = self._procs, []
            # taking the tmpdir under the same lock keeps a straggling
            # _spawn_one from creating its socket in a directory this method is
            # about to rmtree (it fails cleanly on None instead)
            tmpdir, self._tmpdir = self._tmpdir, None
            ring, self._ring = self._ring, None
            hub, self._hub = self._hub, None
            self._child_by_idx = {}
            self._inflight_attempts = {}
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass
        if hub is not None:
            # after the per-link closes, before reaping: a child mid-redial
            # sees connection-refused and exits on its own ceiling; stragglers
            # are killed below either way
            hub.close()
        for p in procs:
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001
                p.kill()
        if ring is not None:
            # AFTER children are reaped (no live writer) and BEFORE returning:
            # every slab is unlinked here, so /dev/shm is clean even if a consumer
            # abandoned leased batches mid-stream
            ring.close()
        if tmpdir:
            shutil.rmtree(tmpdir, ignore_errors=True)


def make_executor(reader_pool_type="thread", workers_count=4, results_queue_size=16,
                  results_timeout_s=300.0, serializer="pickle", worker_respawns=None,
                  shm_slab_bytes=None, shm_slabs=None, io_options=None,
                  recovery=None, transport=None):
    """Factory matching the reference's ``reader_pool_type`` kwarg ('thread'|'process'|'dummy').

    ``serializer`` selects the process-pool wire format: 'pickle'|'arrow' (reference
    Pickle/ArrowTable serializer parity, socket frames) or the shared-memory slab
    family 'shm'/'shm-arrow' (+ '-view' variants — zero-copy read-only delivery; see
    petastorm_tpu/serializers.py); thread/dummy pools share memory and ignore it.
    ``worker_respawns`` bounds the process pool's elastic recovery (dead children are
    replaced and their item re-dispatched up to this many times; 0 = fail fast).
    ``shm_slab_bytes``/``shm_slabs`` size the slab ring (defaults: 32 MB ×
    (workers_count + 2); also tunable via the PTPU_SHM_SLAB_BYTES env var).
    ``io_options`` (:class:`petastorm_tpu.io.IoOptions`) configures the dispatch
    side of the async read path: the per-worker lookahead claim (= readahead
    depth) and work stealing.
    ``recovery`` (:class:`petastorm_tpu.recovery.RecoveryOptions`) is the unified
    recovery policy (ISSUE 7): the process pool's respawn budget defaults from it
    (an explicit ``worker_respawns`` still wins), and every pool applies its
    ``on_poison``/``poison_attempts`` quarantine policy to failing items.
    ``transport`` selects the process pool's wire (ISSUE 15): ``'pipe'`` (the
    default — today's unix-socket connection, byte-identical) or ``'tcp'``
    (framed crc-trailered loopback/LAN sockets that survive link death with
    exactly-once-or-quarantined re-dispatch; also via ``PTPU_TRANSPORT``).
    Thread/dummy pools share memory and ignore it.
    """
    from petastorm_tpu.io import IoOptions
    from petastorm_tpu.transport import normalize_transport

    # validated for EVERY pool type: a typo'd transport (or PTPU_TRANSPORT)
    # must fail loudly at the factory, not be silently ignored because the
    # pool happened to be thread/dummy
    transport = normalize_transport(transport)
    io_options = IoOptions.normalize(io_options)
    lookahead = io_options.lookahead
    stealing = io_options.work_stealing
    if reader_pool_type in ("dummy", "sync"):
        return SyncExecutor(lookahead=lookahead, recovery=recovery)
    if reader_pool_type == "thread":
        return ThreadExecutor(workers_count, results_queue_size, results_timeout_s,
                              lookahead=lookahead, work_stealing=stealing,
                              recovery=recovery)
    if reader_pool_type == "process":
        return ProcessExecutor(workers_count, results_queue_size, results_timeout_s,
                               serializer=serializer, worker_respawns=worker_respawns,
                               shm_slab_bytes=shm_slab_bytes, shm_slabs=shm_slabs,
                               lookahead=lookahead, work_stealing=stealing,
                               recovery=recovery, transport=transport)
    raise ValueError(
        "Unknown reader_pool_type %r (expected 'thread', 'process' or 'dummy')"
        % reader_pool_type
    )
