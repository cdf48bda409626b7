"""The program's spans and programs in a trace (``program_trace.py``), the metrics
that read the loader's phase totals, and the trace reduction's pinned values on the
recorded chip traces. Runs on the CPU, outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import gzip
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import program_trace  # noqa: E402
import trace_reduce  # noqa: E402
from test_trace_reduce import RECORDED, US, _plane  # noqa: E402

PINS = os.path.join(HERE, "data", "reduce_pins.json")


def _recorded(path):
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        return ProfileData.from_serialized_xspace(gzip.decompress(f.read()))


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_reduction_is_pinned(path):
    """Every key of the trace reduction reads on the recorded traces what it read
    when they were recorded (``data/reduce_pins.json``): the program's spans and
    named programs leave ``breakdown`` and the device shares as they were, and a
    trace of a program without them reduces to empty program keys."""
    profile = _recorded(path)
    with open(PINS) as f:
        pinned = json.load(f)[os.path.basename(path)]
    assert trace_reduce.reduce_profile(profile) == pinned
    r = program_trace.reduce_profile(profile)
    assert r["spans"] == r["idle_by_span"] == r["programs"] == {}


def _host_lines(lines, names, stat_names):
    """A host plane whose events carry int64 stats: ``lines`` is
    ``[(line name, [(metadata id, start us, duration us, {stat: value})])]``."""
    meta = "".join('  event_metadata { key: %d value { id: %d name: "%s" } }\n'
                   % (k, k, v) for k, v in names.items())
    meta += "".join('  stat_metadata { key: %d value { id: %d name: "%s" } }\n'
                    % (k, k, v) for k, v in stat_names.items())
    ids = {v: k for k, v in stat_names.items()}
    body = ""
    for lid, (lname, events) in enumerate(lines, start=1):
        evs = ""
        for m, s, d, stats in events:
            st = "".join(" stats { metadata_id: %d int64_value: %d }" % (ids[k], v)
                         for k, v in stats.items())
            evs += "    events { metadata_id: %d offset_ps: %d duration_ps: %d%s }\n" \
                % (m, s * US, d * US, st)
        body += '  lines { id: %d name: "%s" timestamp_ns: 0\n%s  }\n' % (lid, lname, evs)
    return 'planes { id: 1 name: "/host:CPU"\n%s%s}\n' % (body, meta)


def _program_trace():
    """Window 0..100 us. Chip 0 runs stage-2 20..35 and a step 60..70 (idle 0..20,
    35..60, 70..100); chip 1 is busy throughout. The transfer thread decodes 10..40
    (its put 12..18, 1,000 bytes) and transfers 40..65 (its put 41..45, 500 bytes);
    a pool thread works 0..90 and again 100..120, outside the window."""
    from jax.profiler import ProfileData

    names = {1: "jit_bench_step_stem(42)", 2: "jit_ptpu_jpeg_stage2(7)", 3: "fusion.1",
             4: "convolution.2"}
    dev0 = [("XLA Modules", [(2, 20, 20), (1, 60, 10)]),
            ("XLA Ops", [(3, 20, 15), (4, 60, 10)])]
    dev1 = [("XLA Modules", [(1, 0, 100)]), ("XLA Ops", [(4, 0, 100)])]
    text = "".join(_plane(10 + i, "/device:TPU:%d" % i, lines, names)
                   for i, lines in enumerate([dev0, dev1]))
    host = {100: "bench.window", 200: "ptpu.decode.dispatch", 201: "ptpu.decode.put",
            202: "ptpu.h2d.transfer", 203: "ptpu.h2d.put", 204: "ptpu.pool.item"}
    text += _host_lines([
        ("python3", [(100, 0, 100, {})]),
        ("python3", [(200, 10, 30, {"batch": 0}), (201, 12, 6, {"bytes": 1000}),
                     (202, 40, 25, {"batch": 0}), (203, 41, 4, {"bytes": 500})]),
        ("python3", [(204, 0, 90, {"rows": 128}), (204, 100, 20, {"rows": 128})]),
    ], host, {1: "batch", 2: "bytes", 3: "rows"})
    return ProfileData.from_text_proto(text)


def test_program_spans_idle_under_them_and_named_programs():
    r = program_trace.reduce_profile(_program_trace())
    assert r["window_s"] == pytest.approx(100e-6)
    spans = r["spans"]
    assert set(spans) == {"ptpu.decode.dispatch", "ptpu.decode.put",
                          "ptpu.h2d.transfer", "ptpu.h2d.put", "ptpu.pool.item"}
    assert spans["ptpu.decode.dispatch"]["s"] == pytest.approx(30e-6)
    assert spans["ptpu.decode.put"]["bytes"] == 1000
    assert spans["ptpu.h2d.put"]["bytes"] == 500
    # the pool's second item lies outside the window
    assert spans["ptpu.pool.item"] == {"s": pytest.approx(90e-6), "count": 1, "bytes": 0}
    # idle under the transfer thread's spans on chip 0, none on chip 1: the mean
    # over chips halves it; the pool thread is not the transfer thread
    assert r["idle_by_span"] == pytest.approx({"ptpu.decode.dispatch": 15e-6 / 2,
                                               "ptpu.decode.put": 6e-6 / 2,
                                               "ptpu.h2d.transfer": 20e-6 / 2,
                                               "ptpu.h2d.put": 4e-6 / 2})
    assert r["programs"] == pytest.approx({"ptpu_jpeg_stage2": 15e-6 / 2})


def test_program_trace_reads_a_kept_trace_file(tmp_path):
    """The command-line form reads a gzipped trace and prints the keys."""
    import subprocess

    out = subprocess.run([sys.executable, os.path.join(os.path.dirname(HERE),
                                                       "program_trace.py"),
                          RECORDED[0]], capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout)
    assert r["window_s"] > 0 and r["spans"] == {}


def _metric(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.value


PHASE_METRICS = {"decode_pack_share": "decode_pack_s", "decode_put_share": "decode_put_s",
                 "decode_launch_share": "decode_launch_s",
                 "h2d_stage_share": "h2d_stage_s", "h2d_put_share": "h2d_put_s",
                 "h2d_wait_share": "h2d_wait_s"}


@pytest.mark.parametrize("name", sorted(PHASE_METRICS))
def test_phase_metrics_read_the_loader_phase_totals(name):
    rec = {"window_s": 4.0, "rows": 512,
           "stats": {PHASE_METRICS[name]: 1.0, "h2d_bytes": 1024}}
    assert _metric(name)(rec) == pytest.approx(0.25)


def test_bytes_per_row_reads_the_shipped_bytes():
    rec = {"window_s": 4.0, "rows": 512, "stats": {"h2d_bytes": 1024}}
    assert _metric("h2d_bytes_per_row")(rec) == pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted(PHASE_METRICS) + ["h2d_bytes_per_row"])
def test_phase_metrics_fall_silent_without_the_phases(name):
    """A program that predates the phases has no such ``PipelineStats`` fields:
    the metric gives no value, and the reading raises nothing."""
    rec = {"window_s": 4.0, "rows": 512, "stats": {"decode_s": 1.0, "h2d_s": 1.0}}
    assert _metric(name)(rec) is None


def test_phase_metrics_read_what_the_loader_records():
    """The names the metrics read are the loader's ``PipelineStats`` fields."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    from petastorm_tpu.loader import PipelineStats

    fields = set(PipelineStats.__slots__)
    assert set(PHASE_METRICS.values()) | {"h2d_bytes"} <= fields
