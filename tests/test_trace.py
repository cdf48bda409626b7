"""Pipeline span tracing (SURVEY §6: reference has "no spans, no per-stage timers";
this build exports chrome-trace spans for the same stages PipelineStats totals)."""
import json
import threading

import numpy as np
import pytest

from petastorm_tpu.loader import DataLoader
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.trace import TraceRecorder


def test_loader_records_all_stage_spans(scalar_dataset, tmp_path):
    tracer = TraceRecorder()
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1,
                               shuffle_row_groups=False, workers_count=1)
    seen_rows = 0
    with DataLoader(reader, 10, trace=tracer) as loader:
        for batch in loader:
            with tracer.span("train.step"):
                seen_rows += len(np.asarray(batch["id"]))
    assert seen_rows > 0
    names = {e["name"] for e in tracer.events()}
    assert {"reader.next", "batch.form", "decode.dispatch", "h2d.transfer",
            "wait.host_queue", "wait.device_queue", "train.step"} <= names
    # spans come from distinct pipeline threads (producer / transfer / consumer)
    threads = {e["thread"] for e in tracer.events()}
    assert len(threads) >= 3, threads
    for e in tracer.events():
        assert e["duration_s"] >= 0 and e["start_s"] >= 0

    # chrome trace-event JSON round trip
    path = tracer.dump(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert meta and spans
    assert {m["args"]["name"] for m in meta} == threads
    for s in spans:
        assert s["ts"] >= 0 and s["dur"] >= 0 and s["pid"] and s["tid"]


def test_inmem_loader_trace(scalar_dataset):
    """InMemDataLoader records fill-pipeline spans (via the inner DataLoader) plus a
    gather span per served batch."""
    from petastorm_tpu.loader import InMemDataLoader

    tracer = TraceRecorder()
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1,
                               shuffle_row_groups=False, workers_count=1)
    with InMemDataLoader(reader, batch_size=10, num_epochs=1, trace=tracer) as loader:
        batches = sum(1 for _ in loader)
    names = {e["name"] for e in tracer.events()}
    assert "reader.next" in names  # fill pipeline spans
    assert "inmem.gather" in names
    gathers = [e for e in tracer.events() if e["name"] == "inmem.gather"]
    assert len(gathers) == batches


def test_trace_disabled_is_default(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1, workers_count=1)
    with DataLoader(reader, 10) as loader:
        assert loader._trace is None
        next(iter(loader))


def test_recorder_bounded_memory():
    """max_events is a ring: long runs keep the newest window instead of growing
    without bound (review r4)."""
    tracer = TraceRecorder(max_events=10)
    for i in range(25):
        tracer.add("s%d" % i, float(i), 0.5)
    assert len(tracer) == 10
    assert [e["name"] for e in tracer.events()] == ["s%d" % i for i in range(15, 25)]


def test_same_thread_name_distinct_lanes(tmp_path):
    """Two live threads sharing a NAME (train + eval loaders both spawn
    'ptpu-loader') must land on distinct chrome-trace tids, or their overlapping
    spans render as bogus nested slices (review r4)."""
    tracer = TraceRecorder()
    barrier = threading.Barrier(2)

    def worker():
        barrier.wait()
        for _ in range(5):
            with tracer.span("work"):
                pass

    threads = [threading.Thread(target=worker, name="ptpu-loader") for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    path = tracer.dump(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 2  # one lane per thread IDENT
    assert all(m["args"]["name"] == "ptpu-loader" for m in meta)
    span_tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(span_tids) == 2


def test_recorder_thread_safety():
    tracer = TraceRecorder()

    def hammer():
        for i in range(200):
            with tracer.span("t%d" % (i % 3)):
                pass

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tracer) == 800


# -- cross-process merge (ISSUE 3) ------------------------------------------------------


def _negate(x):
    return -x


def test_merged_child_process_dump_has_distinct_pid_lanes(tmp_path):
    """One Perfetto dump must contain spans from the DRIVER threads and from at
    least one pool CHILD process, on distinct pid lanes, and round-trip through
    json.load as valid trace-event JSON (ISSUE-3 acceptance)."""
    import os

    from petastorm_tpu.plan import EpochPlan
    from petastorm_tpu.workers import ProcessExecutor

    tracer = TraceRecorder()
    with ProcessExecutor(workers_count=2, results_queue_size=4) as ex:
        ex.set_trace(tracer)
        ex.start(_negate, EpochPlan(list(range(8)), num_epochs=1))
        with tracer.span("driver.drain"):
            got = sorted(ex.results())
    assert got == sorted(-x for x in range(8))

    evs = tracer.events()
    child_pids = {e["pid"] for e in evs if e["name"] == "child.work"}
    assert child_pids and os.getpid() not in child_pids
    assert {e["name"] for e in evs} >= {"child.work", "child.serialize",
                                        "driver.drain"}

    path = tracer.dump(str(tmp_path / "merged.json"))
    doc = json.load(open(path))  # valid trace-event JSON round trip
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    span_pids = {e["pid"] for e in spans}
    assert os.getpid() in span_pids  # driver lane present
    assert span_pids & child_pids    # child pid lane(s) present
    procs = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs[os.getpid()] == "ptpu-driver"
    assert any(name.startswith("ptpu-pool-child") for name in procs.values())
    # clock alignment: every child span lands inside the driver's drain window
    drain = next(e for e in spans if e["name"] == "driver.drain")
    slack = 0.5e6  # us: child may start an item just before the drain span opens
    for e in spans:
        if e["pid"] in child_pids:
            assert drain["ts"] - slack <= e["ts"] \
                <= drain["ts"] + drain["dur"] + slack, e


def test_child_spans_discarded_without_a_recorder():
    """No tracer attached: the piggybacked child spans are dropped at the driver
    (the disabled path stays one `is not None` check per result)."""
    from petastorm_tpu.plan import EpochPlan
    from petastorm_tpu.workers import ProcessExecutor

    with ProcessExecutor(workers_count=1, results_queue_size=4) as ex:
        ex.start(_negate, EpochPlan([1, 2, 3], num_epochs=1))
        assert sorted(ex.results()) == [-3, -2, -1]


# -- the same stages on the JAX profiler's clock -----------------------------------------

#: the loader's stages, each a ``ptpu.<name>`` host event carrying ``batch=``
_LOADER_STAGES = ("reader.next", "batch.form", "wait.host_queue_put", "wait.host_queue",
                  "decode.dispatch", "h2d.transfer", "wait.device_queue")
#: phase spans and the stage each lies inside, on the same thread
_PHASES = {"decode.pack": "decode.dispatch", "decode.put": "decode.dispatch",
           "decode.launch": "decode.dispatch", "h2d.stage": "h2d.transfer",
           "h2d.put": "h2d.transfer", "h2d.wait": "h2d.transfer"}


def _profiled(tmp_path, run):
    """Run ``run()`` under ``jax.profiler`` and return its result and the host's
    ``ptpu.*`` events as ``(thread line, name, start_ns, end_ns, args)``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    log_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(log_dir)
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(log_dir + "/plugins/profile/*/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("ptpu."):
                        events.append((line.name, ev.name[len("ptpu."):], ev.start_ns,
                                       ev.end_ns, dict(ev.stats)))
    return out, events


def test_stages_on_the_profiler_clock_with_batch_numbers(tmp_path, monkeypatch):
    """A device-decode loader traced by ``jax.profiler``: every stage is a
    ``ptpu.<stage>`` host event with its host-batch number, the decode and H2D
    phases nest inside their stage on its thread, the pool's spans are there,
    and ``trace=``, ``PipelineStats`` and the histograms see the same stages."""
    pytest.importorskip("cv2")
    from petastorm_tpu.io import staging
    from petastorm_tpu.obs.metrics import MetricsRegistry
    from test_common import create_test_jpeg_dataset

    # the CPU backend's device_put aliases host memory, so the pinned staging
    # copy (and its h2d.stage and h2d.wait phases) is refused there; this test
    # reads the spans, not the delivered bytes
    monkeypatch.setattr(staging, "device_put_aliases_host", lambda: False)

    ds = create_test_jpeg_dataset("file://" + str(tmp_path / "ds"), num_rows=24)
    tracer, registry = TraceRecorder(), MetricsRegistry()

    def run():
        reader = make_batch_reader(ds.url, decode_on_device=True, num_epochs=1,
                                   shuffle_row_groups=False, workers_count=1)
        with DataLoader(reader, 8, trace=tracer, metrics=registry,
                        staging=True) as loader:
            n = sum(1 for _ in loader)
            return n, loader.stats.snapshot(), registry.snapshot()

    (n, stats, snap), events = _profiled(tmp_path, run)
    assert n == 3
    names = {e[1] for e in events}
    assert set(_LOADER_STAGES) | set(_PHASES) | {"pool.item", "pool.stage1"} <= names
    for line, name, _s, _e, args in events:
        if name in _LOADER_STAGES:
            assert isinstance(args.get("batch"), int), (name, args)
    # one batch's producer, transfer and consumer spans share its number
    for stage in ("wait.host_queue_put", "decode.dispatch", "h2d.transfer"):
        assert sorted(a["batch"] for _l, nm, _s, _e, a in events if nm == stage) \
            == [0, 1, 2], stage
    assert {a["batch"] for _l, nm, _s, _e, a in events
            if nm == "wait.device_queue"} >= {0, 1, 2}
    for line, name, s, e, _a in events:
        if name in _PHASES:
            assert any(pl == line and pn == _PHASES[name] and ps <= s and e <= pe
                       for pl, pn, ps, pe, _ in events), name
    put_bytes = [a.get("bytes") for _l, nm, _s, _e, a in events if nm == "decode.put"]
    assert put_bytes and all(b > 0 for b in put_bytes)
    assert all(a.get("rows", 0) > 0 for _l, nm, _s, _e, a in events if nm == "pool.item")
    # the other sinks got the same occurrences from the same clock pair
    recorded = [e["name"] for e in tracer.events()]
    for stage, field in (("decode.dispatch", "decode_s"), ("h2d.transfer", "h2d_s")):
        spans = [(e - s) * 1e-9 for _l, nm, s, e, _a in events if nm == stage]
        assert recorded.count(stage) == len(spans) == 3
        assert sum(spans) == pytest.approx(stats[field], abs=0.01, rel=0.05)
    # the phase totals are the phase spans' durations, and the bytes they shipped
    from petastorm_tpu.loader import _PHASES as phase_fields

    for name, field in phase_fields.items():
        spans = [(e - s) * 1e-9 for _l, nm, s, e, _a in events if nm == name]
        assert sum(spans) == pytest.approx(stats[field], abs=0.01, rel=0.05), name
    assert stats["h2d_bytes"] == sum(a.get("bytes", 0) for _l, nm, _s, _e, a in events
                                     if nm in ("decode.put", "h2d.put")) > 0
    for stage, hist in (("reader.next", "read"), ("decode.dispatch", "decode"),
                        ("h2d.transfer", "h2d"), ("wait.device_queue",
                                                  "device_queue_wait")):
        count = snap['ptpu_pipeline_stage_seconds{stage="%s"}' % hist]["count"]
        assert count == recorded.count(stage) \
            == sum(1 for e in events if e[1] == stage), stage


def test_device_inflate_stage_on_the_profiler_clock(tmp_path):
    """A pass-through store's inflate stage lands on the profiler's clock too,
    tagged with the batch it inflates."""
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    root = str(tmp_path / "pagedec")
    os.makedirs(root)
    n = 1200
    pq.write_table(pa.table({"feat": np.repeat(np.arange(30, dtype=np.float32), 40),
                             "cat": np.arange(n, dtype=np.int64) % 7}),
                   os.path.join(root, "part-0.parquet"), compression="snappy",
                   row_group_size=400)

    def run():
        with make_batch_reader("file://" + root, workers_count=1,
                               shuffle_row_groups=False,
                               io_options={"pagedec": "on"}) as reader:
            with DataLoader(reader, 400) as loader:
                return sum(1 for _ in loader)

    n_batches, events = _profiled(tmp_path, run)
    inflates = [a for _l, nm, _s, _e, a in events if nm == "decode.device_inflate"]
    assert n_batches == 3
    assert sorted(a["batch"] for a in inflates) == [0, 1, 2]


def test_decode_pack_span_says_threads_and_runs(tmp_path):
    """``ptpu.decode.pack`` carries how wide the pack ran (``threads``) and how
    many contiguous source runs it read: 2 for a batch across two row groups."""
    cv2 = pytest.importorskip("cv2")
    from petastorm_tpu.ops import jpeg as j
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(7)
    blobs = [cv2.imencode(".jpg", rng.randint(0, 256, (16, 24, 3), dtype=np.uint8))[1]
             .tobytes() for _ in range(8)]
    batch = j.entropy_decode_jpeg_batch(blobs[:5])[1:] + \
        j.entropy_decode_jpeg_batch(blobs[5:])

    _out, events = _profiled(tmp_path, lambda: np.asarray(j.decode_jpeg_batch(batch)))
    packs = [a for _l, nm, _s, _e, a in events if nm == "decode.pack"]
    assert packs == [{"threads": j._pack_threads(len(batch)), "runs": 2}]
