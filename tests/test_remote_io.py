"""Remote read tier (ISSUE 8): byte-gap coalescing, footer cache, hedged
ranged GETs, tiered admission, and the cloud-latency simulator."""
from __future__ import annotations

import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from petastorm_tpu.io.coalesce import plan_byte_ranges, plan_runs, slice_ranges
from petastorm_tpu.io.footercache import FooterCache, FooterEntry
from petastorm_tpu.io.remote import (
    LatencyModel,
    RemoteIoOptions,
    RemoteReadEngine,
    column_chunk_ranges,
    fs_is_remote,
    size_class,
)
from petastorm_tpu.obs.metrics import MetricsRegistry, default_registry


def _write_dataset(root, files=2, groups_per_file=4, rows_per_group=16,
                   row_bytes=512):
    rows = files * groups_per_file * rows_per_group
    per_file = rows // files
    written = 0
    for i in range(files):
        ids = np.arange(written, written + per_file, dtype=np.int64)
        payload = [bytes([j % 251]) * row_bytes for j in ids]
        pq.write_table(pa.table({"id": ids, "payload": payload}),
                       os.path.join(root, "part-%02d.parquet" % i),
                       row_group_size=rows_per_group)
        written += per_file
    return sorted(os.path.join(root, n) for n in os.listdir(root))


def _counter_value(name):
    return default_registry().snapshot().get(name, 0)


# --------------------------------------------------------------------------------------
# byte-gap coalescing planners
# --------------------------------------------------------------------------------------


class TestBytePlanners:
    def test_plan_merges_within_gap_and_splits_at_target(self):
        plan = plan_byte_ranges([(0, 100), (150, 50), (1000, 10)],
                                min_gap_bytes=64, target_request_bytes=120)
        # 0-200 merged (gap 50 <= 64), split at 120; 1000 alone
        assert plan == [(0, 120), (120, 80), (1000, 10)]

    def test_plan_refuses_oversized_gap(self):
        plan = plan_byte_ranges([(0, 10), (100, 10)], min_gap_bytes=50)
        assert plan == [(0, 10), (100, 10)]

    def test_plan_handles_overlap_and_empty(self):
        assert plan_byte_ranges([]) == []
        assert plan_byte_ranges([(0, 10), (5, 20)]) == [(0, 25)]

    def test_plan_covers_every_input_byte(self):
        ranges = [(7, 13), (40, 5), (100, 200), (305, 10)]
        plan = plan_byte_ranges(ranges, min_gap_bytes=8,
                                target_request_bytes=64)
        covered = set()
        for off, ln in plan:
            covered.update(range(off, off + ln))
        for off, ln in ranges:
            assert set(range(off, off + ln)) <= covered

    def test_slice_back_is_byte_identical(self):
        blob = bytes(range(256)) * 4
        ranges = [(3, 17), (100, 60), (900, 50)]
        plan = plan_byte_ranges(ranges, min_gap_bytes=128,
                                target_request_bytes=48)
        chunks = [(off, blob[off:off + ln]) for off, ln in plan]
        out = slice_ranges(chunks, ranges)
        for (off, ln), got in zip(ranges, out):
            assert bytes(got) == blob[off:off + ln]

    def test_slice_short_coverage_fails_loud(self):
        with pytest.raises(ValueError, match="cover"):
            slice_ranges([(0, b"abc")], [(0, 10)])

    def test_plan_runs_gap_ok_bridges_non_adjacent(self):
        class P:
            def __init__(self, path, rg):
                self.path, self.row_group = path, rg

        a, b, c = P("f", 0), P("f", 2), P("f", 7)
        runs = plan_runs([(a, None), (b, None), (c, None)], max_run=4,
                         gap_ok=lambda prev, piece: piece.row_group
                         - prev.row_group <= 3)
        assert [[p.row_group for p in pieces] for pieces, _ in runs] == \
            [[0, 2], [7]]
        # without the predicate: strict adjacency only (PR 4 behavior)
        runs = plan_runs([(a, None), (b, None)], max_run=4)
        assert len(runs) == 2


# --------------------------------------------------------------------------------------
# footer cache
# --------------------------------------------------------------------------------------


class TestFooterCache:
    def test_miss_then_hit_and_spans(self, tmp_path):
        import pyarrow.fs as pafs

        paths = _write_dataset(str(tmp_path), files=1)
        cache = FooterCache(registry=MetricsRegistry())
        try:
            fs = pafs.LocalFileSystem()
            entry = cache.get(fs, paths[0])
            assert isinstance(entry, FooterEntry)
            assert entry.num_row_groups == 4
            assert entry.row_group_rows == (16, 16, 16, 16)
            # spans are increasing and within the file
            spans = [entry.row_group_span(i) for i in range(4)]
            assert all(s[0] < s[1] for s in spans)
            assert all(spans[i][1] <= spans[i + 1][0] for i in range(3))
            again = cache.get(fs, paths[0])
            assert again is entry
            stats = cache.stats()
            assert stats["footer_cache_hits"] == 1
            assert stats["footer_cache_misses"] == 1
        finally:
            cache.clear()

    def test_size_mismatch_invalidates(self, tmp_path):
        import pyarrow.fs as pafs

        paths = _write_dataset(str(tmp_path), files=1)
        cache = FooterCache(registry=MetricsRegistry())
        try:
            fs = pafs.LocalFileSystem()
            cache.get(fs, paths[0])
            assert cache.lookup(paths[0],
                                size=os.path.getsize(paths[0])) is not None
            assert cache.lookup(paths[0], size=12345) is None  # invalidated
            assert cache.stats()["footer_cache_invalidations"] == 1
            assert not cache.contains(paths[0])
        finally:
            cache.clear()

    def test_byte_budget_evicts_oldest(self, tmp_path):
        import pyarrow.fs as pafs

        paths = _write_dataset(str(tmp_path), files=3)
        fs = pafs.LocalFileSystem()
        probe = FooterCache(registry=MetricsRegistry())
        try:
            nbytes = probe.get(fs, paths[0]).nbytes
        finally:
            probe.clear()
        cache = FooterCache(budget_bytes=2 * nbytes + nbytes // 2,
                            registry=MetricsRegistry())
        try:
            for p in paths:
                cache.get(fs, p)
            stats = cache.stats()
            assert stats["footer_cache_evictions"] >= 1
            assert stats["footer_cache_entries"] < 3
            assert cache.peek(paths[-1]) is not None  # newest survives
        finally:
            cache.clear()

    def test_peek_does_not_count(self, tmp_path):
        import pyarrow.fs as pafs

        paths = _write_dataset(str(tmp_path), files=1)
        cache = FooterCache(registry=MetricsRegistry())
        try:
            assert cache.peek(paths[0]) is None
            cache.get(pafs.LocalFileSystem(), paths[0])
            before = cache.stats()["footer_cache_hits"]
            assert cache.peek(paths[0]) is not None
            assert cache.stats()["footer_cache_hits"] == before
        finally:
            cache.clear()

    def test_parquet_file_open_with_cached_footer_reads_no_metadata(
            self, tmp_path):
        """The integration the cache exists for: a ParquetFile opened with
        the cached metadata issues ZERO reads until row-group data is
        asked for."""
        import pyarrow.fs as pafs

        paths = _write_dataset(str(tmp_path), files=1)
        cache = FooterCache(registry=MetricsRegistry())
        try:
            entry = cache.get(pafs.LocalFileSystem(), paths[0])
        finally:
            cache.clear()  # the parsed FileMetaData below outlives the cache

        reads = []

        class Counting:
            def __init__(self, path):
                self._f = open(path, "rb")

            def read(self, n=None):
                reads.append(n)
                return self._f.read(n)

            def seek(self, pos, whence=0):
                return self._f.seek(pos, whence)

            def tell(self):
                return self._f.tell()

            def size(self):
                return os.path.getsize(paths[0])

            def close(self):
                self._f.close()

            closed = False

            def readable(self):
                return True

            def seekable(self):
                return True

            def writable(self):
                return False

        pf = pq.ParquetFile(pa.PythonFile(Counting(paths[0]), mode="r"),
                            metadata=entry.metadata)
        assert reads == []
        table = pf.read_row_group(1, columns=["id"])
        assert table.num_rows == 16
        assert len(reads) >= 1  # data reads only


# --------------------------------------------------------------------------------------
# cloud simulator
# --------------------------------------------------------------------------------------


class TestCloudLatencyFS:
    def test_deterministic_and_attempt_sensitive(self, tmp_path):
        import pyarrow.fs as pafs

        from petastorm_tpu.io.latencyfs import CloudLatencyFS

        fs1 = CloudLatencyFS(pafs.LocalFileSystem(), seed=3, sleep=False)
        fs2 = CloudLatencyFS(pafs.LocalFileSystem(), seed=3, sleep=False)
        d1 = fs1.delay_for("p", 0, 100, 1)
        assert d1 == fs2.delay_for("p", 0, 100, 1)
        assert d1 != fs1.delay_for("p", 0, 100, 2)  # a hedge rolls fresh dice
        assert fs1.delay_for("p", 0, 100, 1) != \
            CloudLatencyFS(pafs.LocalFileSystem(), seed=4,
                           sleep=False).delay_for("p", 0, 100, 1)

    def test_accounting_and_footer_window(self, tmp_path):
        import pyarrow.fs as pafs

        from petastorm_tpu.io.latencyfs import CloudLatencyFS

        paths = _write_dataset(str(tmp_path), files=1)
        fs = CloudLatencyFS(pafs.LocalFileSystem(), sleep=False)
        with fs.open_input_file(paths[0]) as f:
            f.seek(0)
            f.read(10)
        size = os.path.getsize(paths[0])
        assert fs.request_count() == 1
        assert fs.requests[0]["offset"] == 0 and fs.requests[0]["nbytes"] == 10
        assert fs.footer_requests({paths[0]: size}, 64) == []
        with fs.open_input_file(paths[0]) as f:
            f.seek(size - 8)
            f.read(8)
        assert len(fs.footer_requests({paths[0]: size}, 64)) == 1

    def test_pickles_for_process_pools(self):
        import pickle

        import pyarrow.fs as pafs

        from petastorm_tpu.io.latencyfs import CloudLatencyFS

        fs = CloudLatencyFS(pafs.LocalFileSystem(), seed=1, sleep=False)
        fs.delay_for("p", 0, 1, 1)
        clone = pickle.loads(pickle.dumps(fs))
        assert clone.requests == []
        assert clone.delay_for("p", 0, 100, 1) == fs.delay_for("p", 0, 100, 1)

    def test_type_name_marks_remote(self):
        import pyarrow.fs as pafs

        from petastorm_tpu.io.latencyfs import CloudLatencyFS, LatencyFS

        local = pafs.LocalFileSystem()
        assert not fs_is_remote(local)
        assert not fs_is_remote(LatencyFS(local, 0.0))  # delegates 'local'
        assert fs_is_remote(CloudLatencyFS(local, sleep=False))


# --------------------------------------------------------------------------------------
# remote engine
# --------------------------------------------------------------------------------------


def _engine_opts(**over):
    base = dict(enabled=True, hedge=False, footer_cache_bytes=0,
                min_gap_bytes=4096, target_request_bytes=1 << 20)
    base.update(over)
    return RemoteIoOptions(**base)


class TestRemoteEngine:
    def test_read_row_groups_byte_identical(self, tmp_path):
        import pyarrow.fs as pafs

        paths = _write_dataset(str(tmp_path), files=1)
        engine = RemoteReadEngine(pafs.LocalFileSystem(),
                                  options=_engine_opts(),
                                  registry=MetricsRegistry(),
                                  latency_model=LatencyModel(MetricsRegistry()))
        try:
            table, entry = engine.read_row_groups(paths[0], [1, 3], None)
            direct = pq.ParquetFile(paths[0]).read_row_groups([1, 3])
            assert table.equals(direct)
            assert entry.row_group_rows[1] == 16
            stats = engine.stats()
            assert stats["remote_gets"] >= 1
            assert stats["remote_sparse_fallbacks"] == 0
            assert stats["remote_footer_fetches"] == 1  # no cache attached
        finally:
            engine.shutdown()

    def test_column_pruning_fetches_fewer_bytes(self, tmp_path):
        import pyarrow.fs as pafs

        # uncompressed + incompressible-sized payload so the column-chunk
        # byte ranges dominate the footer tail GET
        rng = np.random.default_rng(0)
        ids = np.arange(64, dtype=np.int64)
        payload = [rng.bytes(4096) for _ in ids]
        path = os.path.join(str(tmp_path), "part-00.parquet")
        pq.write_table(pa.table({"id": ids, "payload": payload}), path,
                       row_group_size=16, compression="NONE")
        paths = [path]
        registry = MetricsRegistry()
        engine = RemoteReadEngine(pafs.LocalFileSystem(),
                                  options=_engine_opts(min_gap_bytes=0),
                                  registry=registry,
                                  latency_model=LatencyModel(MetricsRegistry()))
        try:
            table, _ = engine.read_row_groups(paths[0], [0], ["id"])
            assert table.column_names == ["id"]
            pruned_bytes = engine.stats()["remote_bytes"]
            engine2 = RemoteReadEngine(
                pafs.LocalFileSystem(), options=_engine_opts(min_gap_bytes=0),
                registry=MetricsRegistry(),
                latency_model=LatencyModel(MetricsRegistry()))
            try:
                engine2.read_row_groups(paths[0], [0], None)
                full_bytes = engine2.stats()["remote_bytes"]
            finally:
                engine2.shutdown()
            # both pay one footer tail GET; the payload column dwarfs id
            assert pruned_bytes < full_bytes / 2
            assert engine.stats()["remote_sparse_fallbacks"] == 0
        finally:
            engine.shutdown()

    def test_footer_cache_attached_fetches_once(self, tmp_path):
        import pyarrow.fs as pafs

        paths = _write_dataset(str(tmp_path), files=1)
        cache = FooterCache(registry=MetricsRegistry())
        engine = RemoteReadEngine(pafs.LocalFileSystem(),
                                  options=_engine_opts(),
                                  footer_cache=cache,
                                  registry=MetricsRegistry(),
                                  latency_model=LatencyModel(MetricsRegistry()))
        try:
            engine.read_row_groups(paths[0], [0], None)
            engine.read_row_groups(paths[0], [1], None)
            engine.read_row_groups(paths[0], [2], None)
            assert engine.stats()["remote_footer_fetches"] == 1
            assert cache.stats()["footer_cache_hits"] >= 2
        finally:
            engine.shutdown()
            cache.clear()

    def test_column_chunk_ranges_match_top_level_names(self, tmp_path):
        paths = _write_dataset(str(tmp_path), files=1)
        md = pq.read_metadata(paths[0])
        all_ranges = column_chunk_ranges(md, [0], None)
        id_ranges = column_chunk_ranges(md, [0], ["id"])
        assert len(all_ranges) == 2 and len(id_ranges) == 1
        assert column_chunk_ranges(md, [0], ["nope"]) == []

    def test_size_class_buckets(self):
        assert size_class(1) == "64KB"
        assert size_class(100 << 10) == "256KB"
        assert size_class(64 << 20) == ">16MB"

    def test_error_propagates_when_all_attempts_fail(self, tmp_path):
        import pyarrow.fs as pafs

        engine = RemoteReadEngine(pafs.LocalFileSystem(),
                                  options=_engine_opts(),
                                  registry=MetricsRegistry(),
                                  latency_model=LatencyModel(MetricsRegistry()))
        try:
            with pytest.raises(FileNotFoundError):
                engine.fetch_ranges(str(tmp_path / "missing.bin"), [(0, 10)])
        finally:
            engine.shutdown()


class _SlowFirstAttemptFS:
    """First GET of each range sleeps ``slow_s``; repeats are fast — the
    deterministic tail the hedge must beat. Per-range attempt counting keyed
    like CloudLatencyFS's."""

    type_name = "testremote"

    def __init__(self, payload, slow_s=0.5):
        self._payload = payload
        self._slow_s = slow_s
        self._lock = threading.Lock()
        self._attempts = {}
        self.attempt_log = []

    def open_input_file(self, path):
        fs = self

        class F:
            def __init__(self):
                self._pos = 0
                self.closed = False

            def seek(self, pos, whence=0):
                self._pos = pos
                return pos

            def tell(self):
                return self._pos

            def size(self):
                return len(fs._payload)

            def read(self, n=None):
                start = self._pos
                n = len(fs._payload) - start if n is None else n
                with fs._lock:
                    key = (path, start, n)
                    attempt = fs._attempts.get(key, 0) + 1
                    fs._attempts[key] = attempt
                    fs.attempt_log.append((start, n, attempt))
                if attempt == 1:
                    time.sleep(fs._slow_s)
                data = fs._payload[start:start + n]
                self._pos += len(data)
                return data

            def close(self):
                self.closed = True

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                self.close()

            def readable(self):
                return True

            def seekable(self):
                return True

            def writable(self):
                return False

        return F()


def _warmed_model(store, nbytes, fast_s=0.002, n=32):
    model = LatencyModel(MetricsRegistry())
    for _ in range(n):
        model.observe(store, nbytes, fast_s)
    return model


class TestHedging:
    def test_hedge_fires_wins_and_loser_releases_lease(self):
        payload = bytes(range(256)) * 16
        fs = _SlowFirstAttemptFS(payload, slow_s=0.6)
        model = _warmed_model("testremote", 512)
        opts = _engine_opts(hedge=True, hedge_min_samples=8, hedge_min_s=0.01,
                            hedge_quantile=0.9)
        engine = RemoteReadEngine(fs, options=opts, registry=MetricsRegistry(),
                                  latency_model=model)
        acquired0 = _counter_value("ptpu_lease_acquired_total")
        released0 = _counter_value("ptpu_lease_released_total")
        leaked0 = _counter_value("ptpu_lease_leaked_total")
        try:
            t0 = time.perf_counter()
            out = engine.fetch_ranges("blob", [(64, 512)])
            elapsed = time.perf_counter() - t0
            # exactly one copy, byte-correct, and it arrived via the hedge —
            # far sooner than the 0.6 s the stuck primary takes
            assert bytes(out[0]) == payload[64:64 + 512]
            assert elapsed < 0.4
            stats = engine.stats()
            assert stats["remote_hedges"] == 1
            assert stats["remote_hedge_wins"] == 1
            # drain the loser: the slow primary is still sleeping; once it
            # lands it must release its lease without delivering
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                acq = _counter_value("ptpu_lease_acquired_total") - acquired0
                rel = _counter_value("ptpu_lease_released_total") - released0
                if acq == 2 and rel == 2:
                    break
                time.sleep(0.02)
            assert acq == 2 and rel == 2, (acq, rel)
            assert _counter_value("ptpu_lease_leaked_total") == leaked0
        finally:
            engine.shutdown()

    def test_queued_gets_are_not_hedged(self):
        """The hedge deadline runs from EXECUTION start, not submit: a GET
        parked behind a saturated pool is waiting on us, not on a slow
        replica — hedging it would double-load the same pool."""
        payload = bytes(range(256)) * 8
        fs = _SlowFirstAttemptFS(payload, slow_s=0.0)
        slow_once = [True]
        orig_open = fs.open_input_file

        def open_input_file(path):
            f = orig_open(path)
            orig_read = f.read

            def read(n=None):
                if slow_once[0]:
                    slow_once[0] = False
                    time.sleep(0.4)  # only the FIRST GET executed is slow
                return orig_read(n)

            f.read = read
            return f

        fs.open_input_file = open_input_file
        model = _warmed_model("testremote", 256)
        opts = _engine_opts(hedge=True, hedge_min_samples=8, hedge_min_s=0.01,
                            max_inflight=1)
        engine = RemoteReadEngine(fs, options=opts, registry=MetricsRegistry(),
                                  latency_model=model)
        try:
            out = engine.fetch_ranges("blob", [(0, 256), (256, 256), (512, 256)])
            assert [bytes(o) for o in out] == \
                [payload[0:256], payload[256:512], payload[512:768]]
            # only the genuinely slow first GET hedged; the two ranges that
            # merely QUEUED behind it (max_inflight=1) did not
            assert engine.stats()["remote_hedges"] == 1
        finally:
            engine.shutdown()

    def test_no_hedge_below_min_samples(self):
        payload = b"x" * 1024
        fs = _SlowFirstAttemptFS(payload, slow_s=0.05)
        model = LatencyModel(MetricsRegistry())  # cold: no deadline
        engine = RemoteReadEngine(
            fs, options=_engine_opts(hedge=True, hedge_min_samples=20),
            registry=MetricsRegistry(), latency_model=model)
        try:
            out = engine.fetch_ranges("blob", [(0, 100)])
            assert bytes(out[0]) == payload[:100]
            assert engine.stats()["remote_hedges"] == 0
        finally:
            engine.shutdown()

    def test_hedge_loser_drained_under_chaos_latency_at_io_remote(self):
        """ISSUE 8 satellite: chaos latency injection at the ``io.remote``
        hook site delays the PRIMARY attempt; the duplicate wins, the loser's
        lease is released, and the range is delivered exactly once."""
        from petastorm_tpu import chaos
        from petastorm_tpu.chaos.plan import FaultPlan, FaultRule

        payload = bytes(reversed(range(256))) * 8
        fs = _SlowFirstAttemptFS(payload, slow_s=0.0)  # chaos adds the delay
        model = _warmed_model("testremote", 256)
        opts = _engine_opts(hedge=True, hedge_min_samples=8, hedge_min_s=0.01)
        engine = RemoteReadEngine(fs, options=opts, registry=MetricsRegistry(),
                                  latency_model=model)
        plan = FaultPlan([FaultRule("io.remote", "latency", item_key="#primary",
                                    latency_s=0.5, times=1)])
        acquired0 = _counter_value("ptpu_lease_acquired_total")
        released0 = _counter_value("ptpu_lease_released_total")
        try:
            with chaos.armed(plan, propagate=False):
                out = engine.fetch_ranges("blob", [(32, 256)])
            assert bytes(out[0]) == payload[32:32 + 256]
            assert len(plan.injections()) == 1
            assert engine.stats()["remote_hedge_wins"] == 1
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                acq = _counter_value("ptpu_lease_acquired_total") - acquired0
                rel = _counter_value("ptpu_lease_released_total") - released0
                if acq == 2 and rel == 2:
                    break
                time.sleep(0.02)
            assert (acq, rel) == (2, 2)
            # exactly-once: one payload delivered for one requested range
            assert len(out) == 1
        finally:
            engine.shutdown()


# --------------------------------------------------------------------------------------
# tiered admission
# --------------------------------------------------------------------------------------


class TestTieredAdmission:
    def _funnel(self, tmp_path, disk_admit="always", single_epoch=False,
                mem_bytes=1 << 20):
        from petastorm_tpu.cache import LocalDiskCache
        from petastorm_tpu.io.memcache import MemCache, _Store
        from petastorm_tpu.io.tiers import TieredCache

        disk = LocalDiskCache(str(tmp_path / "disk"))
        mem = None
        if mem_bytes:
            store = _Store()
            mem = MemCache(mem_bytes, store=store)
        return TieredCache(mem=mem, disk=disk, disk_admit=disk_admit,
                           single_epoch=single_epoch), disk

    def _disk_entries(self, tmp_path):
        d = tmp_path / "disk"
        return [n for n in os.listdir(d) if not n.endswith(".tmp")]

    def test_tier_attribution_mem_disk_remote(self, tmp_path):
        funnel, disk = self._funnel(tmp_path)
        fills = []

        def fill():
            fills.append(1)
            return {"x": np.arange(8)}

        v1 = funnel.get("k", fill)  # remote fill, admitted to mem AND disk
        assert len(fills) == 1
        v2 = funnel.get("k", fill)  # mem hit
        assert len(fills) == 1
        np.testing.assert_array_equal(v1["x"], v2["x"])
        stats = funnel.stats()
        assert stats["tier_remote_hits"] == 1
        assert stats["tier_mem_hits"] == 1
        assert len(self._disk_entries(tmp_path)) == 1
        # evict mem: the disk tier serves (and re-admits to mem)
        funnel.clear()
        v3 = funnel.get("k", fill)
        assert len(fills) == 1  # served from disk, not refilled
        np.testing.assert_array_equal(v1["x"], np.asarray(v3["x"]))
        assert funnel.stats()["tier_disk_hits"] == 1

    def test_scan_resistant_skips_disk_for_single_epoch(self, tmp_path):
        funnel, _ = self._funnel(tmp_path, disk_admit="scan-resistant",
                                 single_epoch=True, mem_bytes=0)
        funnel.get("k", lambda: {"x": np.arange(4)})
        assert self._disk_entries(tmp_path) == []
        assert funnel.stats()["tier_remote_hits"] == 1

    def test_scan_resistant_skips_disk_when_mem_admits(self, tmp_path):
        funnel, _ = self._funnel(tmp_path, disk_admit="scan-resistant",
                                 single_epoch=False, mem_bytes=1 << 20)
        v = funnel.get("k", lambda: {"x": np.arange(4)})
        assert self._disk_entries(tmp_path) == []  # mem serves it; no dup
        v2 = funnel.get("k", lambda: pytest.fail("must hit mem"))
        np.testing.assert_array_equal(np.asarray(v["x"]), np.asarray(v2["x"]))

    def test_scan_resistant_disk_admits_what_mem_rejects(self, tmp_path):
        """A payload too big for the mem tier must still earn its disk slot —
        otherwise it is cached in NO tier and refetched remotely every
        epoch."""
        funnel, _ = self._funnel(tmp_path, disk_admit="scan-resistant",
                                 single_epoch=False, mem_bytes=64)
        big = {"x": np.arange(1024, dtype=np.int64)}  # 8 KB >> 64 B mem budget
        funnel.get("k", lambda: big)
        assert len(self._disk_entries(tmp_path)) == 1  # disk took it
        v = funnel.get("k", lambda: pytest.fail("disk must serve"))
        np.testing.assert_array_equal(np.asarray(v["x"]), big["x"])
        assert funnel.stats()["tier_disk_hits"] == 1

    def test_scan_resistant_still_serves_disk_hits(self, tmp_path):
        always, _ = self._funnel(tmp_path, disk_admit="always", mem_bytes=0)
        always.get("k", lambda: {"x": np.arange(4)})
        assert len(self._disk_entries(tmp_path)) == 1
        resistant, _ = self._funnel(tmp_path, disk_admit="scan-resistant",
                                    single_epoch=True, mem_bytes=0)
        v = resistant.get("k", lambda: pytest.fail("disk must serve"))
        np.testing.assert_array_equal(np.asarray(v["x"]), np.arange(4))
        assert resistant.stats()["tier_disk_hits"] == 1

    def test_get_writable_through_funnel(self, tmp_path):
        funnel, _ = self._funnel(tmp_path)
        v = funnel.get_writable("k", lambda: {"x": np.arange(4)})
        v["x"][0] = 99  # writable: CoW escalation, not the stored entry
        clean = funnel.get("k", lambda: pytest.fail("must hit"))
        assert np.asarray(clean["x"])[0] == 0

    def test_funnel_pickles(self, tmp_path):
        import pickle

        from petastorm_tpu.io.memcache import MemCache
        from petastorm_tpu.io.tiers import TieredCache

        funnel = TieredCache(mem=MemCache(1 << 20), disk=None,
                             disk_admit="scan-resistant", single_epoch=True)
        funnel.get("k", lambda: {"x": np.arange(3)})
        clone = pickle.loads(pickle.dumps(funnel))
        v = clone.get("k2", lambda: {"x": np.arange(2)})
        assert len(np.asarray(v["x"])) == 2


# --------------------------------------------------------------------------------------
# reader integration
# --------------------------------------------------------------------------------------


def _read_all(reader):
    out = []
    for batch in reader:
        out.append((np.asarray(batch.id).tolist(),
                    [bytes(p) for p in batch.payload]))
    return out


class TestReaderIntegration:
    @pytest.fixture()
    def dataset(self, tmp_path):
        _write_dataset(str(tmp_path), files=2, groups_per_file=4)
        return str(tmp_path)

    def _cloud_fs(self, **kw):
        import pyarrow.fs as pafs

        from petastorm_tpu.io.latencyfs import CloudLatencyFS

        kw.setdefault("sleep", False)
        return CloudLatencyFS(pafs.LocalFileSystem(), **kw)

    def test_remote_tier_end_to_end_identity(self, dataset):
        from petastorm_tpu.reader import make_batch_reader

        with make_batch_reader("file://" + dataset, reader_pool_type="dummy",
                               shuffle_row_groups=False, num_epochs=1) as r:
            base = _read_all(r)
        fs = self._cloud_fs()
        with make_batch_reader("file://" + dataset, filesystem=fs,
                               reader_pool_type="thread", workers_count=2,
                               shuffle_row_groups=False, num_epochs=1,
                               io_options=dict(remote=dict(hedge=False))) as r:
            got = sorted(_read_all(r))
        assert got == sorted(base)

    def test_remote_engine_stats_surface_in_io_stats(self, dataset):
        from petastorm_tpu.reader import make_batch_reader

        fs = self._cloud_fs()
        with make_batch_reader("file://" + dataset, filesystem=fs,
                               reader_pool_type="dummy",
                               shuffle_row_groups=False, num_epochs=1,
                               io_options=dict(readahead=False,
                                               remote=dict(hedge=False))) as r:
            _read_all(r)
            stats = r.io_stats()
        assert stats["remote_gets"] > 0
        assert stats["remote_sparse_fallbacks"] == 0
        assert "footer_cache_hits" in stats
        assert stats["tier_remote_hits"] == 8  # every row group filled remote

    def test_reset_rebuilds_remote_engine(self, dataset):
        from petastorm_tpu.reader import make_batch_reader

        fs = self._cloud_fs()
        reader = make_batch_reader("file://" + dataset, filesystem=fs,
                                   reader_pool_type="dummy",
                                   shuffle_row_groups=False, num_epochs=1,
                                   io_options=dict(remote=dict(hedge=False)))
        try:
            first = _read_all(reader)
            reader.reset()
            second = _read_all(reader)
            assert first == second
        finally:
            reader.stop()
            reader.join()

    def test_remote_off_for_local_filesystem(self, dataset):
        from petastorm_tpu.reader import make_batch_reader

        with make_batch_reader("file://" + dataset, reader_pool_type="dummy",
                               shuffle_row_groups=False, num_epochs=1) as r:
            _read_all(r)
            stats = r.io_stats()
        assert "remote_gets" not in stats  # engine never built

    def test_remote_enabled_forces_engine_on_local(self, dataset):
        from petastorm_tpu.reader import make_batch_reader

        with make_batch_reader(
                "file://" + dataset, reader_pool_type="dummy",
                shuffle_row_groups=False, num_epochs=1,
                io_options=dict(remote=dict(enabled=True,
                                            hedge=False))) as r:
            _read_all(r)
            assert r.io_stats()["remote_gets"] > 0

    def test_footer_unreadable_quarantine_surfaces(self, dataset):
        """ISSUE 8 satellite: a quarantined item whose footer was never
        readable (num_rows unknown) is routed through the degradation log and
        surfaced in io_stats instead of silently collapsing to -1."""
        from petastorm_tpu.obs.log import degradation_counts
        from petastorm_tpu.reader import make_batch_reader
        from petastorm_tpu.recovery import QuarantinedItem

        class HeadlessPiece:
            path = "gs://bucket/poison.parquet"
            row_group = 2
            num_rows = None

        with make_batch_reader("file://" + dataset, reader_pool_type="dummy",
                               shuffle_row_groups=False, num_epochs=1) as r:
            before = degradation_counts().get("footer_unreadable", 0)
            marker = QuarantinedItem(
                item=(0, 0, (HeadlessPiece(), 0)),
                error=RuntimeError("boom"), attempts=3, kind="worker")
            r._absorb_quarantine(marker)
            assert r.io_stats()["footer_unreadable"] == 1
            assert degradation_counts()["footer_unreadable"] == before + 1
            entry = r.quarantine_report.entries[0]
            assert entry.num_rows == -1

    def test_quarantine_resolves_rows_from_readable_footer(self, dataset):
        """A piece planned through the KV fast path carries num_rows=-1 by
        design — quarantining it must resolve the REAL count from the (very
        readable) footer, not cry footer_unreadable."""
        from petastorm_tpu.obs.log import degradation_counts
        from petastorm_tpu.reader import make_batch_reader
        from petastorm_tpu.recovery import QuarantinedItem

        real_path = os.path.join(dataset, sorted(
            n for n in os.listdir(dataset) if n.endswith(".parquet"))[0])

        class KvPiece:
            path = real_path
            row_group = 1
            num_rows = -1  # the KV fast path's "planning does not need it"

        with make_batch_reader("file://" + dataset, reader_pool_type="dummy",
                               shuffle_row_groups=False, num_epochs=1) as r:
            before = degradation_counts().get("footer_unreadable", 0)
            marker = QuarantinedItem(
                item=(0, 1, (KvPiece(), 0)),
                error=RuntimeError("boom"), attempts=3, kind="worker")
            r._absorb_quarantine(marker)
            entry = r.quarantine_report.entries[0]
            assert entry.num_rows == 16  # resolved from the footer
            assert "footer_unreadable" not in r.io_stats()
            assert degradation_counts().get("footer_unreadable", 0) == before


# --------------------------------------------------------------------------------------
# options plumbing
# --------------------------------------------------------------------------------------


class TestOptions:
    def test_remote_options_pickle_and_normalize(self):
        import pickle

        from petastorm_tpu.io import IoOptions

        opts = IoOptions(remote=dict(enabled=True, disk_admit="scan-resistant"))
        clone = pickle.loads(pickle.dumps(opts))
        assert clone.remote.enabled is True
        assert clone.remote.disk_admit == "scan-resistant"
        assert RemoteIoOptions.normalize(clone.remote) is clone.remote
        with pytest.raises(TypeError):
            RemoteIoOptions.normalize("nope")
        with pytest.raises(ValueError):
            RemoteIoOptions(disk_admit="sometimes")

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("PTPU_REMOTE", "1")
        monkeypatch.setenv("PTPU_REMOTE_MIN_GAP_BYTES", "1234")
        monkeypatch.setenv("PTPU_TIER_DISK_ADMIT", "scan-resistant")
        opts = RemoteIoOptions()
        assert opts.enabled is True
        assert opts.min_gap_bytes == 1234
        assert opts.disk_admit == "scan-resistant"
        monkeypatch.setenv("PTPU_REMOTE", "auto")
        assert RemoteIoOptions().enabled is None

    def test_worker_pickle_drops_engine(self, tmp_path):
        import pickle

        from petastorm_tpu.reader import make_batch_reader

        _write_dataset(str(tmp_path), files=1)
        with make_batch_reader(
                "file://" + str(tmp_path), reader_pool_type="dummy",
                shuffle_row_groups=False, num_epochs=1,
                io_options=dict(remote=dict(enabled=True,
                                            hedge=False))) as r:
            _read_all(r)
            worker = r._worker
            assert worker._remote is not None
            clone = pickle.loads(pickle.dumps(worker))
            assert clone._remote is None
            assert clone._remote_unavailable is False
