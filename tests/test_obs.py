"""Observability subsystem (ISSUE 3): metrics registry, exporters, structured
degradation log, and the bottleneck analyzer — including the synthetic-bottleneck
acceptance tests (slow decode => consumer-bound, throttled reader =>
producer-bound) against a real DataLoader pipeline."""
import json
import logging
import os
import time

import numpy as np
import pytest

from petastorm_tpu.loader import DataLoader
from petastorm_tpu.obs.analyze import analyze_snapshot
from petastorm_tpu.obs.export import (
    Reporter,
    parse_prometheus_text,
    read_latest_jsonl_snapshot,
    write_prometheus,
)
from petastorm_tpu.obs.metrics import MetricsRegistry
from petastorm_tpu.reader import make_batch_reader


# -- registry ---------------------------------------------------------------------------


def test_counter_gauge_families_and_labels():
    r = MetricsRegistry()
    a = r.counter("ptpu_events_total", help="events", kind="x")
    b = r.counter("ptpu_events_total", kind="y")
    assert a is not b
    assert a is r.counter("ptpu_events_total", kind="x")  # get-or-create
    a.inc()
    a.inc(4)
    b.inc()
    g = r.gauge("ptpu_depth")
    g.set(3)
    g.inc()
    g.dec(2)
    snap = r.snapshot()
    assert snap['ptpu_events_total{kind="x"}'] == 5
    assert snap['ptpu_events_total{kind="y"}'] == 1
    assert snap["ptpu_depth"] == 2


def test_family_type_conflict_raises():
    r = MetricsRegistry()
    r.counter("ptpu_x_total")
    with pytest.raises(ValueError, match="already registered as counter"):
        r.gauge("ptpu_x_total")
    with pytest.raises(ValueError, match="already registered as counter"):
        r.histogram("ptpu_x_total", stage="read")


def test_histogram_percentiles_without_samples():
    """Log buckets: p50/p90/p99 within one bucket width (~19%) of the truth,
    from O(buckets) memory however many observations."""
    r = MetricsRegistry()
    h = r.histogram("ptpu_lat_seconds", stage="read")
    rng = np.random.RandomState(0)
    samples = np.sort(rng.lognormal(-6, 1.0, 5000))
    for s in samples:
        h.observe(float(s))
    for q in (0.5, 0.9, 0.99):
        true = samples[int(q * len(samples)) - 1]
        est = h.percentile(q)
        assert true <= est <= true * 1.25, (q, true, est)
    snap = h.snapshot()
    assert snap["count"] == 5000
    assert snap["p50"] <= snap["p90"] <= snap["p99"] <= snap["max"]
    assert snap["mean"] == pytest.approx(samples.mean(), abs=1e-6)  # rounded to 6dp


def test_histogram_zero_and_empty():
    r = MetricsRegistry()
    h = r.histogram("ptpu_lat_seconds", stage="x")
    assert h.percentile(0.5) == 0.0  # empty
    h.observe(0.0)
    h.observe(-1.0)
    assert h.percentile(0.99) == 0.0  # all in the underflow bucket
    h.observe(1.0)
    assert h.percentile(0.99) == pytest.approx(1.0)  # capped at the true max


def test_collector_families_and_unregister():
    r = MetricsRegistry()
    handle = r.register_collector("pipeline", lambda: {"read_s": 1.5, "batches": 2})
    r.register_collector("wire", lambda: 1 / 0)  # a dying source must not kill export
    snap = r.snapshot()
    assert snap["ptpu_pipeline_read_s"] == 1.5
    assert snap["ptpu_pipeline_batches"] == 2
    r.unregister_collector(handle)
    assert "ptpu_pipeline_read_s" not in r.snapshot()


# -- exporters --------------------------------------------------------------------------


def _populated_registry():
    r = MetricsRegistry()
    r.counter("ptpu_degradations_total", help="by cause", cause="shm_unsupported").inc(2)
    h = r.histogram("ptpu_pipeline_stage_seconds", help="latency", stage="read")
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    r.gauge("ptpu_depth").set(4)
    r.register_collector("pipeline", lambda: {"rows": 32})
    return r


def test_prometheus_export_parses_and_round_trips(tmp_path):
    r = _populated_registry()
    path = write_prometheus(str(tmp_path / "m.prom"), r)
    with open(path) as f:
        samples = parse_prometheus_text(f.read())
    assert samples['ptpu_degradations_total{cause="shm_unsupported"}'] == 2.0
    assert samples['ptpu_pipeline_stage_seconds_count{stage="read"}'] == 4.0
    assert samples["ptpu_depth"] == 4.0
    assert samples["ptpu_pipeline_rows"] == 32.0
    # histogram buckets are cumulative and end at count
    buckets = sorted((k, v) for k, v in samples.items()
                     if k.startswith("ptpu_pipeline_stage_seconds_bucket"))
    assert buckets, samples
    assert any('le="+Inf"' in k and v == 4.0 for k, v in buckets)


def test_prometheus_parser_rejects_garbage():
    with pytest.raises(ValueError, match="malformed sample"):
        parse_prometheus_text("# TYPE x counter\nx{ 1.0\n")
    with pytest.raises(ValueError, match="no # TYPE"):
        parse_prometheus_text("never_declared 1.0\n")
    with pytest.raises(ValueError, match="non-monotonic"):
        parse_prometheus_text(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\n')


def test_jsonl_reporter_and_stats_cli(tmp_path, capsys):
    r = _populated_registry()
    jsonl = str(tmp_path / "stats.jsonl")
    with Reporter(registry=r, interval_s=600.0, jsonl_path=jsonl):
        pass  # stop() flushes one final snapshot even on an instant run
    obj = read_latest_jsonl_snapshot(jsonl)
    assert obj is not None and "ts" in obj
    assert obj["metrics"]['ptpu_degradations_total{cause="shm_unsupported"}'] == 2
    # a torn final line (live writer) is tolerated
    with open(jsonl, "a") as f:
        f.write('{"ts": 1, "metr')
    assert read_latest_jsonl_snapshot(jsonl)["metrics"] == obj["metrics"]

    from petastorm_tpu.obs.stats_cli import main as stats_main

    assert stats_main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "ptpu_degradations_total" in out
    assert "p50" in out  # histogram summary line
    assert stats_main([str(tmp_path / "missing.jsonl")]) == 1


def test_stats_cli_reads_prometheus_file(tmp_path, capsys):
    path = write_prometheus(str(tmp_path / "m.prom"), _populated_registry())
    from petastorm_tpu.obs.stats_cli import main as stats_main

    assert stats_main([path]) == 0
    assert "ptpu_depth" in capsys.readouterr().out


def test_reporter_periodic_writes(tmp_path):
    r = MetricsRegistry()
    c = r.counter("ptpu_ticks_total")
    jsonl = str(tmp_path / "s.jsonl")
    with Reporter(registry=r, interval_s=0.05, jsonl_path=jsonl,
                  prom_path=str(tmp_path / "s.prom")):
        c.inc()
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            if os.path.exists(jsonl) and os.path.getsize(jsonl) > 0:
                break
            time.sleep(0.02)
    with open(jsonl) as f:  # graftlint: disable=GL-R002 (the getsize above is a readiness poll, not validation — the Reporter is this test's only writer)
        lines = [json.loads(line) for line in f]
    assert lines and all("ts" in obj for obj in lines)
    with open(str(tmp_path / "s.prom")) as f:
        assert parse_prometheus_text(f.read())["ptpu_ticks_total"] >= 1.0


# -- structured degradation log ---------------------------------------------------------


def test_degradation_logs_once_but_counts_every_time(caplog):
    from petastorm_tpu.obs import log as obs_log

    obs_log._reset_announced_for_tests()
    before = obs_log.degradation_counts().get("test_cause_once", 0)
    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.obs"):
        for _ in range(3):
            obs_log.degradation("test_cause_once", "thing degraded (%s)", "why")
    records = [r for r in caplog.records if "test_cause_once" in r.getMessage()]
    assert len(records) == 1  # warn-once
    assert "[degradation cause=test_cause_once]" in records[0].getMessage()
    assert obs_log.degradation_counts()["test_cause_once"] == before + 3


def test_degradation_every_occurrence_mode(caplog):
    from petastorm_tpu.obs import log as obs_log

    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.obs"):
        obs_log.degradation("test_cause_each", "died %d", 1, once=False)
        obs_log.degradation("test_cause_each", "died %d", 2, once=False)
    records = [r for r in caplog.records if "test_cause_each" in r.getMessage()]
    assert len(records) == 2


# -- analyzer: synthetic snapshots ------------------------------------------------------


def test_analyzer_wire_bound_and_balanced_and_idle():
    wire = analyze_snapshot(dict(
        batches=20, read_s=4.0, batch_s=0.1, put_wait_s=0.0, decode_s=0.1,
        h2d_s=0.1, queue_wait_s=3.8, shm_acquire_wait_s=3.5, shm_fallbacks=9))
    assert wire.verdict == "wire-bound"
    assert "slab" in wire.reason
    balanced = analyze_snapshot(dict(
        batches=20, read_s=1.0, batch_s=0.0, put_wait_s=1.0, decode_s=1.0,
        h2d_s=0.0, queue_wait_s=1.0))
    assert balanced.verdict == "balanced"
    assert analyze_snapshot(dict(batches=0)).verdict == "idle"
    # report renders and serializes
    assert "wire-bound" in wire.render()
    assert json.dumps(wire.to_dict())


# -- acceptance: synthetic bottlenecks through a REAL pipeline --------------------------


class _ThrottledReader:
    """Delegating reader proxy that sleeps per delivery — an artificially slow
    producer (parquet/worker side) for the producer-bound acceptance test."""

    def __init__(self, reader, delay_s):
        self._reader = reader
        self._delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self._reader, name)

    def __iter__(self):
        for item in self._reader:
            time.sleep(self._delay_s)
            yield item


def test_throttled_reader_is_producer_bound(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url, num_epochs=3,
                               shuffle_row_groups=False, workers_count=1)
    loader = DataLoader(_ThrottledReader(reader, 0.05), batch_size=5,
                        to_device=False)
    with loader:
        for _ in loader:
            pass
    report = loader.bottleneck_report()
    assert report.verdict == "producer-bound", report.render()
    assert report.utilization["producer"] > report.utilization["consumer"]


def test_slow_decode_stage_is_consumer_bound(scalar_dataset, monkeypatch):
    from petastorm_tpu.transfer import DeviceTransfer

    orig = DeviceTransfer.decode

    def slow_decode(self, batch):
        time.sleep(0.05)  # artificially slow decode dispatch
        return orig(self, batch)

    monkeypatch.setattr(DeviceTransfer, "decode", slow_decode)
    reader = make_batch_reader(scalar_dataset.url, num_epochs=3,
                               shuffle_row_groups=False, workers_count=1)
    loader = DataLoader(reader, batch_size=5, host_queue_size=2, prefetch=1)
    with loader:
        for _ in loader:
            pass
    snap = loader.stats.snapshot()
    assert snap["decode_s"] > 0 and snap["put_wait_s"] > 0
    report = loader.bottleneck_report()
    assert report.verdict == "consumer-bound", report.render()
    assert report.utilization["consumer"] > report.utilization["producer"]


# -- loader metrics integration ---------------------------------------------------------


def test_loader_metrics_disabled_by_default(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1, workers_count=1)
    with DataLoader(reader, 8, to_device=False) as loader:
        next(iter(loader))
        assert loader._obs is None  # disabled path: one `is None` check per site


def test_loader_exports_metric_families(scalar_dataset):
    registry = MetricsRegistry()
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1,
                               shuffle_row_groups=False, workers_count=1)
    with DataLoader(reader, 8, to_device=False, metrics=registry) as loader:
        n = sum(1 for _ in loader)
        snap = registry.snapshot()
    assert n > 0
    # PipelineStats totals migrated onto ptpu_pipeline_* families
    assert snap["ptpu_pipeline_batches"] == n
    assert snap["ptpu_pipeline_rows"] == loader.stats.rows
    assert "ptpu_pipeline_host_queue_depth" in snap
    # stage latency histograms populated per occurrence
    read_hist = snap['ptpu_pipeline_stage_seconds{stage="read"}']
    assert read_hist["count"] > 0
    assert read_hist["p50"] <= read_hist["p99"]
    # the analyzer report carries the percentile detail when metrics are on
    report = loader.bottleneck_report()
    assert report.percentiles and "read" in report.percentiles
    # collectors unregister at __exit__: no stale pipeline families afterwards
    assert "ptpu_pipeline_batches" not in registry.snapshot()
    # ... but the histograms (real registered metrics) survive for post-hoc reads
    assert 'ptpu_pipeline_stage_seconds{stage="read"}' in registry.snapshot()


def test_every_stage_reaches_every_sink_once(scalar_dataset):
    """One timing per stage occurrence feeds every sink that is on: the
    ``trace=`` recorder, the stage histogram (``_STAGES``' histogram key), the
    flight ring and ``PipelineStats`` count each occurrence once, under one
    vocabulary of stage names."""
    from petastorm_tpu.loader import _STAGES
    from petastorm_tpu.obs.health import HealthOptions
    from petastorm_tpu.trace import TraceRecorder

    registry, tracer = MetricsRegistry(), TraceRecorder()
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1,
                               shuffle_row_groups=False, workers_count=1)
    with DataLoader(reader, 8, metrics=registry, trace=tracer,
                    health=HealthOptions(stall_threshold_s=60)) as loader:
        n = sum(1 for _ in loader)
        snap = registry.snapshot()
        flight = [e["name"] for e in loader._health.flight.events()
                  if e["kind"] == "span"]
        stats = {f: getattr(loader.stats, f) for f in type(loader.stats).__slots__}
    recorded = [e["name"] for e in tracer.events()]
    assert n == stats["batches"] > 0
    seen = [name for name in _STAGES if name in recorded]
    assert {"reader.next", "batch.form", "wait.host_queue_put", "wait.host_queue",
            "decode.dispatch", "h2d.transfer", "wait.device_queue"} <= set(seen)
    for name in seen:
        field, hist_key = _STAGES[name][:2]
        hist = snap['ptpu_pipeline_stage_seconds{stage="%s"}' % hist_key]
        assert hist["count"] == recorded.count(name) == flight.count(name), name
        if field is not None:  # the stats total is the sum of the same timings
            assert stats[field] == pytest.approx(sum(
                e["duration_s"] for e in tracer.events() if e["name"] == name)), name
    # the stages of every batch: one decode dispatch and one transfer each
    assert recorded.count("decode.dispatch") == recorded.count("h2d.transfer") == n


def test_collectors_go_quiet_when_loader_is_garbage_collected(scalar_dataset):
    """A loader torn down WITHOUT the context manager (stop/join only) must not
    be pinned alive by the registry, and its collectors must stop exporting
    once it is collected — the weak-reference contract."""
    import gc
    import weakref

    registry = MetricsRegistry()
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1,
                               shuffle_row_groups=False, workers_count=1)
    loader = DataLoader(reader, 8, to_device=False, metrics=registry)
    n = sum(1 for _ in loader)
    assert n > 0
    assert registry.snapshot()["ptpu_pipeline_batches"] == n
    loader.stop()
    loader.join()
    reader.stop()
    reader.join()
    ref = weakref.ref(loader)
    del loader, reader
    gc.collect()
    assert ref() is None  # the registry's collectors hold no strong reference
    assert "ptpu_pipeline_batches" not in registry.snapshot()  # gone, not stale


def test_loader_metrics_prometheus_end_to_end(scalar_dataset, tmp_path):
    registry = MetricsRegistry()
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1,
                               shuffle_row_groups=False, workers_count=1)
    with DataLoader(reader, 8, to_device=False, metrics=registry) as loader:
        n = sum(1 for _ in loader)
        path = write_prometheus(str(tmp_path / "m.prom"), registry)
    with open(path) as f:
        samples = parse_prometheus_text(f.read())
    assert samples["ptpu_pipeline_batches"] == float(n)
    assert any(k.startswith('ptpu_pipeline_stage_seconds_bucket{')
               for k in samples)
