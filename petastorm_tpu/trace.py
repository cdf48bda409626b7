"""Chrome-trace span recording for the data pipeline.

The reference ships no tracing at all (SURVEY.md §6: "no spans, no per-stage timers
in the hot path"); ``PipelineStats`` already gives cheap per-stage TOTALS, and this
module adds the per-span view when you need to see *when* each stage ran: hand a
:class:`TraceRecorder` to ``DataLoader(trace=...)`` and every pipeline stage (reader
fetch, batch formation, device decode dispatch, H2D, queue waits — plus, on the
process pool's shared-memory wire, ``shm.acquire_wait`` spans from driver threads
starved for a free slab) records one
duration event per occurrence, tagged with its thread. Dump with :meth:`dump` and
load the file in ``chrome://tracing`` / Perfetto to see producer, transfer, and
consumer lanes and where the bubbles are.

Overhead when enabled is one ``perf_counter`` pair (already paid for stats) plus an
appended tuple per span — no formatting until :meth:`dump`; disabled (``trace=None``,
the default) it costs one ``is None`` check per span site.

Cross-process merge (ISSUE 3): pool children record spans around each work item
(:mod:`petastorm_tpu._child_worker`) and piggyback them on the result header;
the driver thread folds them in via :meth:`add_child`, clock-aligned through
each child's wall/perf anchor pair (same host, shared wall clock), so one dump
shows driver threads AND worker processes on distinct pid lanes.

    from petastorm_tpu.trace import TraceRecorder

    tracer = TraceRecorder()
    with DataLoader(reader, 256, trace=tracer) as loader:
        for batch in loader:
            with tracer.span("train.step"):
                step(batch)
    tracer.dump("pipeline_trace.json")

The same stages also land on the JAX profiler's own clock, as ``ptpu.<stage>``
host events (:func:`profiler_span`), beside the device's ops in a
``jax.profiler.trace`` capture; the parts of a stage are :class:`phase` spans.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time


def profiler_span(name, **args):
    """``jax.profiler.TraceAnnotation(name, **args)``: a host event on the JAX
    profiler's clock, with ``args`` as its metadata (more can follow through
    the returned span's ``set_metadata``). About half a microsecond when no
    trace is being captured. In a process that has not imported JAX there is
    no profiler to write to: the span is then a null context whose ``as``
    target is None."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name, **args)


#: per thread, the totals the open loader stage's phases add to (``sink``)
_stage_sink = threading.local()


def set_phase_sink(sink):
    """Route this thread's :class:`phase` timings to ``sink.add_phase(name,
    seconds, nbytes)`` (a loader's ``PipelineStats``) until it is set again;
    None stops the routing."""
    _stage_sink.sink = sink


def count(name, n=1):
    """Add ``n`` to the counter ``name`` (a ``PipelineStats`` field) of the
    loader stage open on this thread, through ``sink.add_count``; nothing
    where none is open. Code below the loader counts what it did this way."""
    sink = getattr(_stage_sink, "sink", None)
    if sink is not None:
        sink.add_count(name, n)


#: process-wide totals of work done on threads where no loader stage is open (the
#: reader pool's stage-1 decode): name -> total since the process started
_tallies = {}
_tally_lock = threading.Lock()


def tally(**counts):
    """Add each ``name=n`` to the process-wide totals :func:`tallies` reads. For
    work on the reader pool's threads, which :func:`count` cannot reach: every
    loader of the process folds what the totals grew by into its
    ``PipelineStats`` as it goes."""
    with _tally_lock:
        for name, n in counts.items():
            _tallies[name] = _tallies.get(name, 0) + n


def tallies():
    """A copy of the process-wide totals :func:`tally` adds to."""
    with _tally_lock:
        return dict(_tallies)


class phase:
    """A part of a loader stage: ``ptpu.<name>`` on the profiler's clock (its
    ``args`` as metadata; ``as`` gives the span, for ``set_metadata``, or None
    where JAX is not imported), and its seconds and ``bytes`` arg added to the
    stage's totals when a loader stage is open on this thread. Code below the
    loader (the codecs) marks its phases without knowing the loader."""

    __slots__ = ("name", "nbytes", "span", "t0")

    def __init__(self, name, **args):
        self.name, self.nbytes = name, args.get("bytes", 0)
        self.span = profiler_span("ptpu." + name, **args)

    def __enter__(self):
        span = self.span.__enter__()
        self.t0 = time.perf_counter()
        return span

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.span.__exit__(*exc)
        sink = getattr(_stage_sink, "sink", None)
        if sink is not None:
            sink.add_phase(self.name, dt, self.nbytes)
        return False


class TraceRecorder:
    """Thread-safe duration-event recorder in Chrome trace-event format.

    ``max_events`` bounds memory on long runs (a span is one small tuple, but a
    multi-hour run at hundreds of batches/s would otherwise grow without limit):
    once full, the OLDEST spans are dropped — the dump shows the most recent
    window, which is the one being debugged. ``None`` disables the bound."""

    def __init__(self, max_events=1_000_000):
        from collections import deque

        # (name, lane key (tname, tid), t0_s, dur_s, pid-or-None (None = local))
        self._events = deque(maxlen=max_events)
        #: provenance flow points (ISSUE 10): (flow_id, lane, pid, t, name,
        #: terminate) — rendered as Perfetto flow events ("s"/"t"/"f") linking
        #: one item's spans across pid lanes in the dump
        self._flows = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self._origin = time.perf_counter()
        #: wall-clock instant matching ``_origin`` — the cross-process alignment
        #: anchor (children ship their own (wall, perf) pair; same host, so the
        #: shared wall clock maps child perf_counter values onto this timeline)
        self._wall_origin = time.time()

    def add(self, name, t0, dur):
        """Record one span: ``t0`` from ``time.perf_counter()``, ``dur`` seconds."""
        t = threading.current_thread()
        # keyed by (name, ident): two live threads may SHARE a name (e.g. a train
        # and an eval loader both run a "ptpu-loader" producer) and collapsing them
        # onto one trace lane would render bogus nested slices
        with self._lock:
            self._events.append((name, (t.name, t.ident), t0, dur, None))

    def add_child(self, pid, spans, wall_anchor, perf_anchor, lane=None):
        """Merge spans recorded in a pool child process onto a pid-tagged lane.

        ``spans`` is ``[(name, t0, dur), ...]`` with ``t0`` from the CHILD's
        ``perf_counter``; ``(wall_anchor, perf_anchor)`` is a pair the child
        sampled together, so each span start maps to this recorder's timeline as
        ``wall_anchor + (t0 - perf_anchor)`` on the shared wall clock. Alignment
        error is the wall-clock sampling jitter (microseconds on one host)."""
        if not spans:
            return
        lane = lane or ("ptpu-child-%d" % pid)
        base = (wall_anchor - self._wall_origin) - perf_anchor + self._origin
        with self._lock:
            for name, t0, dur in spans:
                self._events.append((name, (lane, pid), t0 + base, dur, pid))

    def add_flow_point(self, flow_id, lane, pid, t, name="item",
                       terminate=False):
        """Record one point of a Perfetto flow (ISSUE 10: the provenance
        plane's item linkage). ``t`` is a value from THIS recorder's timeline
        (``perf_counter``; child spans are pre-aligned by the provenance
        merge); points sharing ``flow_id`` render as one arrow chain across
        the ``(pid, lane)`` tracks. ``terminate=True`` marks the chain's
        explicit end (the batch delivery point)."""
        with self._lock:
            self._flows.append((int(flow_id), lane, int(pid), t, name,
                                bool(terminate)))

    @contextlib.contextmanager
    def span(self, name):
        """Context manager recording the enclosed block as one span."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, t0, time.perf_counter() - t0)

    def __len__(self):
        with self._lock:
            return len(self._events)

    def events(self):
        """Snapshot of recorded spans as dicts (name/thread/pid/start_s/
        duration_s); ``pid`` is this process for locally recorded spans."""
        with self._lock:
            evs = list(self._events)
        local = os.getpid()
        return [{"name": n, "thread": t[0], "pid": p if p is not None else local,
                 "start_s": t0 - self._origin, "duration_s": d}
                for n, t, t0, d, p in evs]

    def dump(self, path):
        """Write ``chrome://tracing`` / Perfetto JSON (trace-event format).

        Lanes are per (process, thread): locally recorded spans render under
        this process's pid, child spans under THEIR pid with a ``process_name``
        metadata row per child process — one timeline, distinct pid lanes."""
        with self._lock:
            evs = list(self._events)
            flows = list(self._flows)
        local_pid = os.getpid()
        lanes = {}  # (pid, lane key) -> (tid, lane display name)
        for _n, tkey, _t0, _d, p in evs:
            key = (p if p is not None else local_pid, tkey)
            if key not in lanes:
                lanes[key] = tkey[0]
        for _fid, lane, fpid, _t, _n, _term in flows:
            # flow points land on (lane, pid)-keyed tracks like child spans do;
            # a point naming a lane no slice lives on still gets its own track
            key = (fpid, (lane, fpid))
            if key not in lanes:
                lanes[key] = lane
        trace_events = []
        tids = {}
        child_pids = sorted({p for _n, _t, _t0, _d, p in evs if p is not None
                             and p != local_pid}
                            | {fpid for _fid, _l, fpid, _t, _n, _term in flows
                               if fpid != local_pid})
        if child_pids:  # pid lanes only exist on merged multi-process dumps
            for pid in [local_pid] + child_pids:
                trace_events.append({
                    "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
                    "args": {"name": "ptpu-driver" if pid == local_pid
                             else "ptpu-pool-child-%d" % pid}})
        for key in sorted(lanes, key=str):
            tid = tids[key] = len(tids) + 1
            trace_events.append({  # thread-name metadata row
                "ph": "M", "pid": key[0], "tid": tid, "name": "thread_name",
                "args": {"name": lanes[key]}})
        for name, tkey, t0, dur, p in evs:
            pid = p if p is not None else local_pid
            trace_events.append({
                "ph": "X", "pid": pid, "tid": tids[(pid, tkey)], "name": name,
                "ts": (t0 - self._origin) * 1e6, "dur": dur * 1e6, "cat": "pipeline"})
        # provenance flows (ISSUE 10): chain each flow id's points in time
        # order — "s" start, "t" steps, "f" finish — so Perfetto draws arrows
        # linking one item's spans across pid lanes
        by_flow = {}
        for fid, lane, fpid, t, name, term in flows:
            by_flow.setdefault(fid, []).append((t, lane, fpid, name, term))
        for fid, points in by_flow.items():
            points.sort(key=lambda p: p[0])
            for i, (t, lane, fpid, name, term) in enumerate(points):
                if i == 0:
                    ph = "s"
                elif i == len(points) - 1 or term:
                    ph = "f"
                else:
                    ph = "t"
                ev = {"ph": ph, "id": fid, "pid": fpid,
                      "tid": tids[(fpid, (lane, fpid))], "name": name,
                      "ts": (t - self._origin) * 1e6, "cat": "prov"}
                if ph == "f":
                    ev["bp"] = "e"  # bind to the enclosing slice
                trace_events.append(ev)
                if ph == "f":
                    break
        with open(path, "w") as f:
            json.dump({"traceEvents": trace_events,
                       "displayTimeUnit": "ms"}, f)
        return path
