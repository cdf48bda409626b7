"""Buffer-lease contract (ISSUE 6): Lease/LeasedBatch discipline, read-only
views, copy-on-write escalation, pinned H2D staging, the loader's lease-riding
batch path, and revocation across ``Reader.reset()``."""
import gc

import numpy as np
import pytest

from petastorm_tpu.errors import LeaseError, LeaseRevoked
from petastorm_tpu.io.lease import (Lease, LeasedBatch, attach_leases,
                                    copy_census, count_copy, lease_stats,
                                    readonly_view, take_leases)


# -- Lease refcount protocol ------------------------------------------------------------

def test_lease_release_fires_owner_callback_exactly_once_at_zero():
    freed = []
    lease = Lease(release_cb=lambda: freed.append(1))
    lease.retain()
    lease.retain()
    lease.release()
    lease.release()
    assert freed == []  # holders remain
    lease.release()
    assert freed == [1]  # last holder out: reclaim fired once
    assert not lease.alive


def test_lease_double_release_raises_lease_error():
    lease = Lease()
    lease.release()
    with pytest.raises(LeaseError):
        lease.release()


def test_lease_retain_after_full_release_raises():
    lease = Lease()
    lease.release()
    with pytest.raises(LeaseError):
        lease.retain()


def test_lease_revoke_keeps_refcounts_but_fails_accessors():
    freed = []
    lease = Lease(release_cb=lambda: freed.append(1))
    lease.retain()
    lease.revoke()
    assert lease.revoked
    with pytest.raises(LeaseRevoked):
        lease.check()
    # holders still release balanced; the owner reclaim still fires
    lease.release()
    lease.release()
    assert freed == [1]


def test_lease_gc_reclaim_counts_leak_and_frees_owner():
    freed = []
    before = lease_stats()["leaked"]
    lease = Lease(release_cb=lambda: freed.append(1))  # graftlint: disable=GL-L001 (the leak IS the subject under test)
    del lease
    gc.collect()
    assert freed == [1]  # the owner's pool cannot wedge on an abandoned hold
    assert lease_stats()["leaked"] == before + 1  # but the drop is counted


# -- LeasedBatch ------------------------------------------------------------------------

def _leased_batch():
    arr = np.arange(8, dtype=np.int64)
    view = arr.view()
    view.flags.writeable = False
    lease = Lease(kind="test")
    return LeasedBatch({"x": view, "y": np.arange(4.0)}, [lease]), lease, arr


def test_leased_batch_access_after_revoke_raises_not_garbage():
    batch, lease, _arr = _leased_batch()
    np.testing.assert_array_equal(batch["x"], np.arange(8))
    lease.revoke()
    with pytest.raises(LeaseRevoked):
        batch["x"]
    batch.release()


def test_leased_batch_bulk_accessors_check_revocation():
    """items()/values()/get() hand out buffer views too — after revocation they
    must raise like __getitem__, not serve views into recycled memory."""
    batch, lease, _arr = _leased_batch()
    assert set(dict(batch.items())) == {"x", "y"}
    assert len(list(batch.values())) == 2
    assert batch.get("x") is not None
    lease.revoke()
    with pytest.raises(LeaseRevoked):
        batch.items()
    with pytest.raises(LeaseRevoked):
        batch.values()
    with pytest.raises(LeaseRevoked):
        batch.get("x")
    batch.release()


def test_leased_batch_writable_is_cow_escalation():
    batch, lease, arr = _leased_batch()
    before = copy_census().get("lease_cow", 0)
    owned = batch.writable("x")
    assert owned.flags.writeable
    owned[:] = -1
    np.testing.assert_array_equal(arr, np.arange(8))  # source untouched
    assert batch["x"] is owned  # the batch now carries the owned copy
    assert copy_census().get("lease_cow", 0) == before + owned.nbytes
    # already-writable columns escalate for free (no copy, no census charge)
    assert batch.writable("y") is batch["y"]
    batch.release()
    assert not lease.alive


def test_leased_batch_release_is_idempotent_at_batch_level():
    batch, lease, _arr = _leased_batch()
    batch.release()
    batch.release()  # graftlint: disable=GL-L001 (batch-level release is documented idempotent — the idempotence IS the subject under test)
    assert not lease.alive


def test_attach_and_take_leases_roundtrip():
    lease = Lease(kind="test")
    plain = {"x": np.arange(3)}
    assert attach_leases(plain, []) is plain  # no-op without leases
    batch = attach_leases(plain, [lease])
    assert isinstance(batch, LeasedBatch)
    taken = take_leases(batch)
    assert taken == (lease,)
    assert take_leases(batch) == ()  # ownership moved exactly once
    assert take_leases({"x": 1}) == ()  # plain dicts have none
    lease.release()


def test_readonly_view_shares_buffers_and_freezes_elements():
    inner = np.arange(6, dtype=np.float32)
    ragged = np.empty(2, dtype=object)
    ragged[0] = np.arange(3)
    ragged[1] = np.arange(5.0)
    src = {"flat": inner, "ragged": ragged, "rows": [{"v": np.ones(2)}],
           "s": "keep"}
    out = readonly_view(src)
    assert out["flat"].base is inner  # zero-copy view
    assert not out["flat"].flags.writeable
    assert not out["ragged"][0].flags.writeable  # elements frozen too
    assert out["s"] == "keep"
    assert not out["rows"][0]["v"].flags.writeable
    inner[0] = 42.0  # shared buffer: the view sees the owner's writes
    assert out["flat"][0] == 42.0
    # fresh outer containers: element reassignment stays consumer-local
    out["ragged"][0] = None
    assert ragged[0] is not None


def test_copy_census_accumulates_per_site():
    before = copy_census().get("loader_concat", 0)
    count_copy("loader_concat", 128)
    count_copy("loader_concat", 0)  # zero-byte charges are dropped
    assert copy_census()["loader_concat"] == before + 128


# -- PinnedStagingPool ------------------------------------------------------------------

def test_staging_pool_stage_roundtrip_and_slab_reuse():
    from petastorm_tpu.io.staging import PinnedStagingPool

    pool = PinnedStagingPool(1 << 16, num_slabs=1, acquire_timeout_s=0.2)
    try:
        before = copy_census().get("h2d_stage", 0)
        arrays = {"a": np.arange(64, dtype=np.float64),
                  "b": np.full((8, 8), 7, np.int32), "meta": "host"}
        staged, lease = pool.stage(arrays)
        assert lease is not None
        np.testing.assert_array_equal(staged["a"], arrays["a"])
        np.testing.assert_array_equal(staged["b"], arrays["b"])
        assert staged["meta"] == "host"  # non-ndarrays pass through
        assert not staged["a"].flags.writeable  # nothing writes under DMA
        assert copy_census()["h2d_stage"] == \
            before + arrays["a"].nbytes + arrays["b"].nbytes
        # the single slab is busy: a second stage falls back...
        again, lease2 = pool.stage({"a": np.arange(4.0)})
        assert lease2 is None and again["a"].flags.writeable
        lease.release()
        # ...and returns after release
        staged3, lease3 = pool.stage({"a": np.arange(4.0)})
        assert lease3 is not None
        lease3.release()
    finally:
        pool.close()


def test_staging_pool_oversized_batch_degrades_to_passthrough():
    from petastorm_tpu.io.staging import PinnedStagingPool
    from petastorm_tpu.obs.log import degradation_counts

    pool = PinnedStagingPool(4096, num_slabs=1)
    try:
        before = degradation_counts().get("staging_oversized", 0)
        arrays = {"big": np.zeros(8192, np.uint8)}
        out, lease = pool.stage(arrays)
        assert lease is None and out["big"] is arrays["big"]
        assert degradation_counts()["staging_oversized"] == before + 1
    finally:
        pool.close()


def test_staging_pool_close_is_idempotent_and_stage_after_close_falls_back():
    from petastorm_tpu.io.staging import PinnedStagingPool

    pool = PinnedStagingPool(4096, num_slabs=1)
    try:
        pool.close()
        out, lease = pool.stage({"a": np.arange(4.0)})
        assert lease is None and out["a"].flags.writeable
    finally:
        pool.close()  # idempotent second close


# -- loader integration: staging decision ------------------------------------------------

def _transfer(staging):
    from petastorm_tpu.loader import PipelineStats
    from petastorm_tpu.transfer import DeviceTransfer

    return DeviceTransfer(PipelineStats(), stage=None, staging=staging)


def test_loader_staging_refused_on_aliasing_backend(monkeypatch):
    """staging=True on a backend whose device_put aliases host numpy must be
    REFUSED with a degradation — recycled slabs would corrupt delivered
    batches — and the loader keeps transferring from pageable memory."""
    import petastorm_tpu.io.staging as staging_mod
    from petastorm_tpu.obs.log import degradation_counts

    monkeypatch.setattr(staging_mod, "_alias_probe", True)
    transfer = _transfer(True)
    before = degradation_counts().get("staging_aliasing", 0)
    pool = transfer._ensure_staging({"x": np.arange(8, dtype=np.float32)})
    assert pool is None and transfer._staging is None
    assert degradation_counts()["staging_aliasing"] == before + 1


def test_loader_staging_disabled_and_auto_cpu_off(monkeypatch):
    import petastorm_tpu.io.staging as staging_mod

    monkeypatch.setattr(staging_mod, "_alias_probe", False)
    for arg in (False, None):  # explicit off; auto mode on the CPU backend
        assert _transfer(arg)._ensure_staging({"x": np.arange(8.0)}) is None


def test_loader_staging_forced_builds_pinned_pool(monkeypatch):
    """staging=True on a copying backend builds the pool sized to the first
    batch, and the staged transfer path stages + releases the slab."""
    import petastorm_tpu.io.staging as staging_mod

    monkeypatch.setattr(staging_mod, "_alias_probe", False)
    transfer = _transfer(True)
    pool = transfer._ensure_staging({"x": np.arange(1024, dtype=np.float32)})
    try:
        assert pool is not None and len(pool) == 2
        assert pool.slab_bytes >= 4096
        assert transfer._ensure_staging({"x": np.arange(4.0)}) is pool  # cached
    finally:
        transfer.close()


# -- loader lease path end-to-end --------------------------------------------------------

def _drain_host_loader(reader, batch_size, **kwargs):
    from petastorm_tpu.loader import DataLoader

    ids = []
    frozen = []
    with DataLoader(reader, batch_size=batch_size, to_device=False,
                    last_batch="drop", **kwargs) as loader:
        for batch in loader:
            ids.extend(np.asarray(batch["id"]).tolist())
            frozen.append(not batch["id"].flags.writeable)
    return ids, frozen


def test_loader_rides_view_wire_leases_without_detach_copies(scalar_dataset):
    """The plain batched path on the view wire RETAINS the delivery's lease
    instead of copying every slab view out: zero loader_detach bytes, batches
    byte-identical to the copying default wire, nothing leaked."""
    from petastorm_tpu.reader import make_batch_reader

    before_census = copy_census()
    before_leases = lease_stats()
    with make_batch_reader(scalar_dataset.url, reader_pool_type="process",
                           workers_count=1, num_epochs=1,
                           shuffle_row_groups=False,
                           wire_serializer="shm-view") as reader:
        view_ids, frozen = _drain_host_loader(reader, batch_size=5)
    gc.collect()
    after_census = copy_census()
    after_leases = lease_stats()
    assert after_census.get("loader_detach", 0) == \
        before_census.get("loader_detach", 0)  # no copy-out pass
    assert after_census.get("wire_writable", 0) == \
        before_census.get("wire_writable", 0)  # no writable-contract copy
    assert after_leases["leaked"] == before_leases["leaked"]
    assert after_leases["active"] <= before_leases["active"]
    assert any(frozen)  # the delivered arrays really were leased views

    with make_batch_reader(scalar_dataset.url, reader_pool_type="process",
                           workers_count=1, num_epochs=1,
                           shuffle_row_groups=False,
                           wire_serializer="shm") as reader:
        default_ids, _ = _drain_host_loader(reader, batch_size=5)
    assert view_ids == default_ids  # byte-identical delivery order and content


def test_loader_view_wire_consumer_mutation_fails_loud(scalar_dataset):
    """A consumer mutating a leased batch in place gets ValueError (read-only
    view), never silent slab corruption; writable() is the sanctioned out."""
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    with make_batch_reader(scalar_dataset.url, reader_pool_type="process",
                           workers_count=1, num_epochs=1,
                           shuffle_row_groups=False,
                           wire_serializer="shm-view") as reader:
        with DataLoader(reader, batch_size=5, to_device=False,
                        last_batch="drop") as loader:
            for batch in loader:
                if isinstance(batch, LeasedBatch):
                    with pytest.raises(ValueError):
                        batch["id"][0] = -1
                    owned = batch.writable("id")
                    owned[0] = -1  # CoW copy: legal, slab untouched
                    break


# -- revocation across Reader.reset() / re-epoch -----------------------------------------

def test_lease_retained_across_reader_reset_raises_lease_revoked(scalar_dataset):
    """ISSUE-6 satellite: a lease retained across the reader's executor rebuild
    must raise a clear LeaseRevoked — never return garbage from a recycled
    slab ring — and iteration after reset() stays correct."""
    from petastorm_tpu.reader import make_batch_reader

    reader = make_batch_reader(scalar_dataset.url, reader_pool_type="process",
                               workers_count=1, num_epochs=1,
                               shuffle_row_groups=False,
                               wire_serializer="shm-view")
    try:
        batch = next(iter(reader))
        lease = reader.take_lease()
        assert lease is not None
        held = LeasedBatch({"id": np.asarray(batch.id)}, [lease.retain()])
        lease.release()  # the reader's delivery hold; ours rides `held`
        np.testing.assert_array_equal(
            held["id"], np.asarray(batch.id))  # valid before reset

        reader.reset()
        with pytest.raises(LeaseRevoked):
            held["id"]  # the executor rebuild recycled the slab ring
        held.release()

        ids = []
        for b in reader:
            ids.extend(np.asarray(b.id).tolist())
        assert sorted(ids) == [r["id"] for r in scalar_dataset.data]
    finally:
        reader.stop()
        reader.join()


def test_view_wire_re_epoch_leases_stay_valid_within_epochs(scalar_dataset):
    """Re-epoch WITHOUT reset: epoch boundaries recycle nothing (the ring
    outlives the plan), so leases stay valid batch to batch across epochs."""
    from petastorm_tpu.reader import make_batch_reader

    with make_batch_reader(scalar_dataset.url, reader_pool_type="process",
                           workers_count=1, num_epochs=2,
                           shuffle_row_groups=False,
                           wire_serializer="shm-view") as reader:
        rows = 0
        for batch in reader:
            rows += len(np.asarray(batch.id))
        assert rows == 2 * len(scalar_dataset.data)


# -- pad-path index cache (ISSUE-6 satellite) --------------------------------------------

def test_pad_index_cache_reused_per_rowcount(scalar_dataset):
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    with make_batch_reader(scalar_dataset.url, num_epochs=1,
                           shuffle_row_groups=False) as reader:
        with DataLoader(reader, batch_size=8, to_device=False,
                        last_batch="pad") as loader:
            batches = list(loader)
    # 30 rows / batch 8 → three full batches + one padded short batch
    assert all(len(b["id"]) == 8 for b in batches)
    tail = batches[-1]
    assert tail["__valid__"].sum() == 30 % 8
    assert tail["__valid__"].dtype == bool
    # the padded region repeats the last valid row
    last_valid = int(np.flatnonzero(tail["__valid__"])[-1])
    np.testing.assert_array_equal(
        np.asarray(tail["id"])[last_valid:],
        np.full(8 - last_valid, np.asarray(tail["id"])[last_valid]))


def test_pad_cache_internal_reuse_and_mask_isolation():
    """The (arange+full) gather index is built once per row count and frozen;
    the delivered __valid__ mask is an owned copy (consumers may mutate it)."""
    from petastorm_tpu.loader import DataLoader

    loader = DataLoader.__new__(DataLoader)
    loader.local_batch_size = 8
    loader._pad_cache = {}
    first = loader._pad({"x": np.arange(5, dtype=np.int64)})
    idx1, valid1 = loader._pad_cache[5]
    assert not idx1.flags.writeable and not valid1.flags.writeable
    second = loader._pad({"x": np.arange(5, dtype=np.int64) * 10})
    assert loader._pad_cache[5] is not None and len(loader._pad_cache) == 1
    idx2, _ = loader._pad_cache[5]
    assert idx2 is idx1  # rebuilt nothing
    np.testing.assert_array_equal(first["x"], [0, 1, 2, 3, 4, 4, 4, 4])
    np.testing.assert_array_equal(second["x"], [0, 10, 20, 30, 40, 40, 40, 40])
    first["__valid__"][0] = False  # owned mask: later batches unaffected
    assert second["__valid__"][0]
    third = loader._pad({"x": np.arange(5)})
    assert third["__valid__"][0]


# -- memcache lease accounting -----------------------------------------------------------

def test_memcache_entry_leases_tracked_and_released_on_eviction():
    from petastorm_tpu.io.memcache import MemCache, _Store

    before = lease_stats()
    cache = MemCache(4096, store=_Store())
    try:
        cache.get("a", lambda: {"x": np.zeros(1024, np.uint8)})
        cache.get("b", lambda: {"x": np.zeros(1024, np.uint8)})
        assert lease_stats()["active"] == before["active"] + 2
        # admitting past the budget evicts LRU entries — their leases release
        cache.get("c", lambda: {"x": np.zeros(3072, np.uint8)})
        assert lease_stats()["active"] < before["active"] + 3
    finally:
        cache.clear()
    assert lease_stats()["active"] == before["active"]
    assert lease_stats()["leaked"] == before["leaked"]


def test_memcache_served_views_survive_eviction_via_refcount():
    """Eviction releases the entry's lease (accounting) but numpy refcounting
    keeps the buffers alive for outstanding served views — no revocation, no
    garbage."""
    from petastorm_tpu.io.memcache import MemCache, _Store

    cache = MemCache(1700, store=_Store())
    try:
        served = cache.get("a", lambda: {"x": np.arange(256, dtype=np.uint8)})
        cache.get("b", lambda: {"x": np.zeros(1536, np.uint8)})  # evicts "a"
        assert not cache.contains("a")
        np.testing.assert_array_equal(served["x"],
                                      np.arange(256, dtype=np.uint8))
    finally:
        cache.clear()
