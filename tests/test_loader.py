"""DataLoader tests: host re-batching, shuffling buffer, sharded device_put, device transforms.

Runs on the conftest 8-virtual-CPU-device topology so NamedSharding paths are exercised
without TPU hardware (SURVEY.md §5).
"""
import numpy as np
import pytest

from petastorm_tpu.loader import DataLoader, make_dataloader
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.transform import TransformSpec


def _collect(loader):
    with loader:
        return list(loader)


def test_host_batches_exact_size(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False)
    loader = DataLoader(reader, batch_size=7, to_device=False)
    batches = _collect(loader)
    assert batches, "no batches yielded"
    for b in batches:
        assert len(b["id"]) == 7  # drop policy: every batch exact
    total = sum(len(b["id"]) for b in batches)
    assert total == (len(scalar_dataset.data) // 7) * 7


def test_partial_last_batch(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False)
    loader = DataLoader(reader, batch_size=7, last_batch="partial", to_device=False)
    batches = _collect(loader)
    total = sum(len(b["id"]) for b in batches)
    assert total == len(scalar_dataset.data)
    all_ids = np.concatenate([np.asarray(b["id"]) for b in batches])
    assert sorted(all_ids.tolist()) == sorted(r["id"] for r in scalar_dataset.data)


def test_pad_last_batch(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False)
    loader = DataLoader(reader, batch_size=8, last_batch="pad", to_device=False)
    batches = _collect(loader)
    for b in batches:
        assert len(b["id"]) == 8
    # valid mask marks the padded tail
    n_valid = sum(int(np.asarray(b["__valid__"]).sum()) for b in batches)
    assert n_valid == len(scalar_dataset.data)


def test_pad_last_batch_consumer_watermark_counts_valid_rows_only(scalar_dataset):
    """ADVICE r5 loader.py:846: under ``last_batch='pad'`` the consumer watermark
    must count only rows the reader DELIVERED (sum of ``__valid__``), never the
    repeated padding — otherwise it overruns the producer's delivered-row log."""
    total = len(scalar_dataset.data)
    reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False)
    loader = DataLoader(reader, batch_size=8, last_batch="pad", to_device=False)
    with loader:
        batches = list(loader)
    assert total % 8 != 0 and len(batches[-1]["id"]) == 8  # padding actually occurred
    assert loader._rows_consumed == total  # not rounded up to a batch multiple
    # the consumer has exactly caught the producer's log: the checkpoint is the
    # final all-delivered state, which a fresh reader restores cleanly
    state = loader.state_dict()
    with make_batch_reader(scalar_dataset.url, shuffle_row_groups=False) as r2:
        r2.load_state_dict(state)
        assert sum(1 for _ in r2) == 0  # nothing left to replay


def test_detach_slab_views_covers_nested_object_elements():
    """Review finding (PR 2): the view-wire detach must copy read-only ELEMENTS
    of object (ragged) columns and detach() staged payloads, not just top-level
    read-only arrays — the outer object array is writable, its slab-view elements
    are not."""
    from petastorm_tpu.io.lease import detach_slab_views

    class _Staged:
        def __init__(self):
            self.detached = False

        def detach(self):
            self.detached = True
            return self

    ro_elem = np.arange(4)
    ro_elem.setflags(write=False)
    ragged = np.empty(3, dtype=object)
    ragged[:] = [ro_elem, np.arange(2), _Staged()]
    ro_flat = np.arange(5, dtype=np.float32)
    ro_flat.setflags(write=False)
    out = detach_slab_views({"ragged": ragged, "flat": ro_flat,
                             "ok": np.arange(3)})
    assert out["flat"].flags.writeable and out["flat"] is not ro_flat
    assert out["ok"].flags.writeable  # already-writable column passes through
    assert out["ragged"][0].flags.writeable and out["ragged"][0] is not ro_elem
    np.testing.assert_array_equal(out["ragged"][0], np.arange(4))
    assert out["ragged"][2].detached  # staged payloads detach from their buffers


def test_shuffling_buffer_changes_order_and_preserves_set(scalar_dataset):
    def ids(shuffle_cap, seed):
        reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False)
        loader = DataLoader(reader, batch_size=5, last_batch="partial",
                            shuffling_queue_capacity=shuffle_cap, seed=seed,
                            to_device=False)
        out = np.concatenate([np.asarray(b["id"]) for b in _collect(loader)])
        return out.tolist()

    plain = ids(0, 0)
    shuffled = ids(20, 1)
    assert sorted(plain) == sorted(shuffled)
    assert plain != shuffled


def test_device_put_default_device(scalar_dataset):
    import jax

    reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False)
    loader = DataLoader(reader, batch_size=4)
    batches = _collect(loader)
    b = batches[0]
    assert isinstance(b["float_col"], jax.Array)
    assert b["float_col"].shape[0] == 4
    # string columns must stay host-side numpy
    if "string_col" in b:
        assert not isinstance(b["string_col"], jax.Array)


def test_device_put_named_sharding(scalar_dataset):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = np.array(jax.devices()[:8]).reshape(8)
    mesh = Mesh(devices, ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False)
    loader = DataLoader(reader, batch_size=16, sharding=sharding)
    batches = _collect(loader)
    b = batches[0]
    arr = b["float_col"]
    assert arr.shape[0] == 16
    assert len(arr.sharding.device_set) == 8
    # each device holds 1/8 of the batch
    shard = arr.addressable_shards[0]
    assert shard.data.shape[0] == 2


def test_device_transform_applied(scalar_dataset):
    spec = TransformSpec(
        func=lambda batch: {**batch, "float_col": batch["float_col"] * 0.0},
        device=True,
    )
    reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False,
                               transform_spec=spec)
    loader = DataLoader(reader, batch_size=4)
    batches = _collect(loader)
    assert float(np.abs(np.asarray(batches[0]["float_col"])).sum()) == 0.0


def test_row_reader_path(synthetic_dataset):
    reader = make_reader(synthetic_dataset.url, shuffle_row_groups=False,
                         schema_fields=["id", "matrix"])
    loader = DataLoader(reader, batch_size=6, last_batch="partial", to_device=False)
    batches = _collect(loader)
    total = sum(len(b["id"]) for b in batches)
    assert total == len(synthetic_dataset.data)
    assert batches[0]["matrix"].shape[1:] == (8, 4)


def test_make_dataloader_convenience(scalar_dataset):
    loader = make_dataloader(scalar_dataset.url, batch_size=5, shuffle_row_groups=False)
    batches = _collect(loader)
    assert len(batches[0]["id"]) == 5


def test_producer_error_propagates(scalar_dataset):
    spec = TransformSpec(func=lambda pdf: 1 / 0)  # raises in worker
    reader = make_batch_reader(scalar_dataset.url, transform_spec=spec)
    loader = DataLoader(reader, batch_size=4, to_device=False)
    with pytest.raises(Exception):
        _collect(loader)


def _write_ragged_dataset(tmp_path, n=24, seed=0):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.RandomState(seed)
    path = tmp_path / "ragged_ds"
    path.mkdir()
    lengths = rng.randint(1, 9, n)
    vectors = [rng.standard_normal(int(k)).astype(np.float32).tolist() for k in lengths]
    table = pa.table({
        "id": np.arange(n, dtype=np.int64),
        "vec": pa.array(vectors, type=pa.list_(pa.float32())),
    })
    pq.write_table(table, str(path / "part-0.parquet"), row_group_size=8)
    return "file://" + str(path), vectors


def test_ragged_field_padded_to_device(tmp_path):
    """SURVEY §8 hard part #2: ragged rows reach the device as fixed-shape arrays with
    a validity mask; values and mask agree with the source."""
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    url, vectors = _write_ragged_dataset(tmp_path)
    reader = make_batch_reader(url, shuffle_row_groups=False, num_epochs=1)
    with DataLoader(reader, batch_size=8, pad_shapes={"vec": (8,)}) as loader:
        total = 0
        for batch in loader:
            vec = np.asarray(batch["vec"])
            mask = np.asarray(batch["vec__mask"])
            ids = np.asarray(batch["id"])
            assert vec.shape == (8, 8) and mask.shape == (8, 8)
            for i, rid in enumerate(ids):
                src = np.asarray(vectors[int(rid)], dtype=np.float32)
                assert mask[i].sum() == len(src)
                np.testing.assert_array_equal(vec[i][: len(src)], src)
                assert (vec[i][len(src):] == 0).all()
                total += 1
        assert total == 24


def test_ragged_field_without_pad_shape_raises(tmp_path):
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    url, _ = _write_ragged_dataset(tmp_path)
    reader = make_batch_reader(url, shuffle_row_groups=False, num_epochs=1)
    with pytest.raises(ValueError, match="pad_shapes"):
        with DataLoader(reader, batch_size=8) as loader:
            next(iter(loader))


def test_ragged_pad_max_exceeded_raises(tmp_path):
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    url, _ = _write_ragged_dataset(tmp_path)
    reader = make_batch_reader(url, shuffle_row_groups=False, num_epochs=1)
    with pytest.raises(ValueError, match="exceeding declared pad max"):
        with DataLoader(reader, batch_size=8, pad_shapes={"vec": (4,)}) as loader:
            list(loader)


def test_transfer_error_propagates_to_device_consumer(scalar_dataset):
    """Errors raised on the transfer thread (decode/device_put) must surface in the
    consumer, not deadlock it — the sentinel is delivered even after the failure."""
    reader = make_batch_reader(scalar_dataset.url)
    loader = DataLoader(reader, batch_size=4, prefetch=2,
                        device_transform=lambda batch: 1 / 0)
    with loader, pytest.raises(ZeroDivisionError):
        for _ in loader:
            pass


def test_abandoned_iterator_stops_pipeline(scalar_dataset):
    """Breaking out of iteration mid-epoch must stop the producer and transfer threads
    (prefetched device batches would otherwise stay pinned for the process lifetime)."""
    import time

    reader = make_batch_reader(scalar_dataset.url, num_epochs=None)
    with DataLoader(reader, batch_size=4, prefetch=2) as loader:
        it = iter(loader)
        next(it)
        del it
        deadline = time.time() + 10
        while time.time() < deadline and (
                loader._transfer_thread.is_alive() or loader._producer.is_alive()):
            time.sleep(0.05)
        assert not loader._transfer_thread.is_alive()
        assert not loader._producer.is_alive()


def test_stats_populate_through_device_path(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url)
    loader = DataLoader(reader, batch_size=8, prefetch=2)
    with loader:
        n = sum(1 for _ in loader)
    snap = loader.stats.snapshot()
    assert snap["batches"] == n > 0
    assert snap["rows"] == n * 8
    assert set(snap) == {"rows", "batches", "read_s", "batch_s", "put_wait_s",
                         "decode_s", "h2d_s",
                         "queue_wait_s", "device_queue_wait_s",
                         "decode_unsharded_batches", "shm_slabs_in_flight",
                         "shm_bytes", "shm_fallbacks", "shm_acquire_wait_s",
                         "decode_pack_s", "decode_put_s", "decode_launch_s",
                         "h2d_stage_s", "h2d_put_s", "h2d_wait_s", "h2d_bytes",
                         "h2d_unwaited_outputs", "h2d_slab_puts",
                         "pack_s", "pack_slots", "pack_pad_slots", "pack_chunks",
                         "decode_batches", "decode_launches", "decode_real_blocks",
                         "decode_canvas_blocks", "decode_host_rows", "crop_boxes",
                         "stage1_images", "stage1_ns", "stage1_symbols",
                         "stage1_fast_symbols"}
    assert snap["pack_s"] == 0 and snap["pack_chunks"] == 0  # no pack=: nothing packed
    # no device decode: nothing launched, nothing cropped
    assert snap["decode_batches"] == snap["decode_launches"] == snap["crop_boxes"] == 0
    assert snap["decode_unsharded_batches"] == 0  # no sharding configured → no fallback
    assert snap["h2d_unwaited_outputs"] == 0  # no device decode: no program outputs
    assert snap["h2d_slab_puts"] == 0  # the CPU backend stages nothing
    assert snap["h2d_bytes"] > 0  # the columns' device_put phase counted its bytes
    assert snap["read_s"] >= 0 and snap["device_queue_wait_s"] >= 0


def test_inmem_loader_epochs_and_shuffle(scalar_dataset):
    """InMemDataLoader: all rows present each epoch, deterministic by seed, epochs
    differ in order, zero reader involvement after construction."""
    from petastorm_tpu.loader import InMemDataLoader

    def ordered_reader():
        # deterministic fill order: seed determinism is relative to the store layout
        return make_batch_reader(scalar_dataset.url, num_epochs=1,
                                 shuffle_row_groups=False, workers_count=1,
                                 reader_pool_type="dummy")

    with InMemDataLoader(ordered_reader(), batch_size=8, num_epochs=2, seed=3,
                         last_batch="partial") as loader:
        n_batches = len(loader)
        epochs = [[], []]
        for i, b in enumerate(loader):
            epochs[i // n_batches].extend(np.asarray(b["id"]).tolist())
    expected = sorted(r["id"] for r in scalar_dataset.data)
    assert sorted(epochs[0]) == expected
    assert sorted(epochs[1]) == expected
    assert epochs[0] != epochs[1]  # reshuffled per epoch

    with InMemDataLoader(ordered_reader(), batch_size=8, num_epochs=2, seed=3,
                         last_batch="partial") as again:
        replay = [np.asarray(b["id"]).tolist() for b in again]
    assert [x for xs in replay for x in xs] == epochs[0] + epochs[1]  # seed-determined


def test_inmem_loader_drop_and_transform(scalar_dataset):
    from petastorm_tpu.loader import InMemDataLoader

    reader = make_batch_reader(scalar_dataset.url, num_epochs=1)
    with InMemDataLoader(reader, batch_size=7, num_epochs=1, shuffle=False,
                         device_transform=lambda b: {**b, "id2": b["id"] * 2}) as loader:
        batches = list(loader)
    total = len(scalar_dataset.data)
    assert len(batches) == total // 7  # drop: only full batches
    for b in batches:
        assert b["id"].shape[0] == 7
        np.testing.assert_array_equal(np.asarray(b["id2"]), np.asarray(b["id"]) * 2)


def test_device_transform_with_key_varies_per_batch(scalar_dataset):
    """A two-arg device_transform receives a fresh fold of the seed per batch —
    the on-device random-augmentation hook."""
    import jax

    def transform(batch, key):
        noise = jax.random.uniform(key, ())
        return {**batch, "noise": noise}

    reader = make_batch_reader(scalar_dataset.url)
    loader = DataLoader(reader, batch_size=8, seed=7, device_transform=transform)
    with loader:
        noises = [float(b["noise"]) for b in loader]
    assert len(set(noises)) == len(noises)  # fresh key each batch

    reader = make_batch_reader(scalar_dataset.url)
    with DataLoader(reader, batch_size=8, seed=7, device_transform=transform) as again:
        replay = [float(b["noise"]) for b in again]
    assert replay == noises  # deterministic in the seed


def test_sequence_sharded_batch_delivery(tmp_path):
    """SURVEY §6: the loader's context-parallel obligation — when the consumer's
    sharding splits the sequence axis (dp×sp), batches arrive laid out that way."""
    import jax
    import pyarrow as pa
    import pyarrow.parquet as pq
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    path = tmp_path / "seq_ds"
    path.mkdir()
    n, seq = 32, 16
    tokens = np.arange(n * seq, dtype=np.int32).reshape(n, seq)
    table = pa.table({
        "id": np.arange(n, dtype=np.int64),
        "tokens": pa.FixedSizeListArray.from_arrays(tokens.reshape(-1), seq),
    })
    pq.write_table(table, str(path / "part-0.parquet"), row_group_size=16)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "sp"))
    sharding = {"tokens": NamedSharding(mesh, P("dp", "sp")),
                "id": NamedSharding(mesh, P("dp"))}
    # dummy pool: multi-worker completion order is not deterministic, and this test
    # asserts exact row content of the first batch
    reader = make_batch_reader("file://" + str(path), shuffle_row_groups=False,
                               num_epochs=1, reader_pool_type="dummy")
    with DataLoader(reader, batch_size=8, sharding=sharding) as loader:
        batch = next(iter(loader))
    arr = batch["tokens"]
    assert arr.shape == (8, seq)
    assert len(arr.sharding.device_set) == 8
    shard = arr.addressable_shards[0]
    assert shard.data.shape == (8 // 2, seq // 4)  # batch over dp, sequence over sp
    np.testing.assert_array_equal(np.asarray(arr), tokens[:8])


def test_inmem_loader_sharded_store_and_batches(scalar_dataset):
    """InMemDataLoader keeps the resident store AND the gathered batches laid out per
    the given sharding (batch axis over dp)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from petastorm_tpu.loader import InMemDataLoader

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1)
    with InMemDataLoader(reader, batch_size=16, num_epochs=1, seed=0,
                         sharding=sharding) as loader:
        batch = next(iter(loader))
    arr = batch["float_col"]
    assert arr.shape[0] == 16
    assert len(arr.sharding.device_set) == 8
    assert arr.addressable_shards[0].data.shape[0] == 2


def test_stop_midstream_joins_promptly(scalar_dataset):
    """ADVICE r2 teardown race: stop() used to be able to consume the producer's
    end-of-stream sentinel while the transfer thread was blocked in an untimed
    queue get — join() then stalled its full 60s timeout. After the fix (sentinel
    re-put after drain) stop+join must complete in seconds regardless of where the
    pipeline threads are blocked."""
    import time

    for taken in (0, 1, 3):
        reader = make_batch_reader(scalar_dataset.url, num_epochs=None)
        with DataLoader(reader, batch_size=4, prefetch=2) as loader:
            it = iter(loader)
            for _ in range(taken):
                next(it)
            t0 = time.perf_counter()
            loader.stop()
            loader.join()
            assert time.perf_counter() - t0 < 15, "join stalled: teardown race regressed"
            if loader._producer is not None:  # taken=0: generator body never ran
                assert not loader._producer.is_alive()
            if loader._transfer_thread is not None:
                assert not loader._transfer_thread.is_alive()
            it.close()


def test_reiteration_restarts_pipeline(scalar_dataset):
    """A second __iter__ supersedes an abandoned first one: pipeline state is reset
    on the consumer thread (ADVICE r2: _stop used to be cleared on the transfer
    thread, racing stop(); re-iteration could leak a live previous thread set)."""
    reader = make_batch_reader(scalar_dataset.url, num_epochs=None,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=5, prefetch=2) as loader:
        it1 = iter(loader)
        next(it1)  # start, then abandon mid-epoch
        it2 = iter(loader)
        first = next(it2)
        assert len(first["id"]) == 5
        # closing the SUPERSEDED iterator runs its finalizer mid-flight of the new
        # iteration; the generation guard must keep it from stopping it2's pipeline
        it1.close()
        for _ in range(6):  # > prefetch+queue depth: proves the pipeline is live
            batch = next(it2)
            assert len(batch["id"]) == 5
        loader.stop()
        loader.join()
        # the superseded iterator's threads must be gone too
        assert not loader._producer.is_alive()
        it2.close()


def test_inmem_partial_tail_sharding(scalar_dataset):
    """ADVICE r2: with sharding + last_batch='partial', the short tail batch is laid
    out per the sharding when its row count divides the batch axis, and yielded
    unsharded (no crash) when it does not."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from petastorm_tpu.loader import InMemDataLoader

    # 30 rows, batch 8 → tail 6. Over a 2-device batch axis 6 divides → sharded tail.
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("dp",))
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1)
    with InMemDataLoader(reader, batch_size=8, num_epochs=1, shuffle=False,
                         sharding=NamedSharding(mesh2, P("dp")),
                         last_batch="partial") as loader:
        batches = list(loader)
    assert len(batches[-1]["id"]) == 6
    assert len(batches[-1]["id"].sharding.device_set) == 2

    # Over an 8-device batch axis 6 does not divide → tail yielded unsharded.
    mesh8 = Mesh(np.array(jax.devices()[:8]), ("dp",))
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1)
    with InMemDataLoader(reader, batch_size=8, num_epochs=1, shuffle=False,
                         sharding=NamedSharding(mesh8, P("dp")),
                         last_batch="partial") as loader:
        batches = list(loader)
    tail = batches[-1]["id"]
    assert len(tail) == 6
    assert len(batches[0]["id"].sharding.device_set) == 8  # full batches still sharded


def test_undecomposable_multiprocess_sharding_raises(scalar_dataset, monkeypatch):
    """VERDICT r2 #5: under multi-process JAX, a PositionalSharding/GSPMD sharding
    whose batch axis cannot be decomposed per process must raise — not silently feed
    every process the GLOBAL batch."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from petastorm_tpu.loader import _resolve_local_batch

    # SingleDeviceSharding carries no mesh structure — the undecomposable class
    sharding = SingleDeviceSharding(jax.devices()[3])
    # single process: fine (no decomposition needed)
    assert _resolve_local_batch(16, sharding) == 16
    # simulate a 2-process topology where the sharding's device is remote
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    with pytest.raises(ValueError, match="cannot decompose the global batch"):
        _resolve_local_batch(16, sharding)
    with pytest.raises(ValueError, match="cannot decompose the global batch"):
        _resolve_local_batch(16, {"x": sharding})
    # a sharding entirely on THIS process's devices stays valid (local placement)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    assert _resolve_local_batch(16, sharding) == 16
    assert _resolve_local_batch(16, {"x": sharding}) == 16


def test_device_shuffle_capacity_exactly_once_and_shuffled(scalar_dataset):
    """VERDICT r2 #4: the HBM exchange shuffle is wired into the loader with
    epoch-honest semantics — every row delivered exactly once per epoch, order
    decorrelated, both when capacity >= dataset and when capacity < dataset."""
    def run(capacity, seed=11):
        reader = make_batch_reader(scalar_dataset.url, shuffle_row_groups=False,
                                   schema_fields=["id", "float_col"],
                                   reader_pool_type="dummy")
        loader = DataLoader(reader, batch_size=5, last_batch="partial",
                            device_shuffle_capacity=capacity, seed=seed)
        with loader:
            batches = list(loader)
        ids = np.concatenate([np.asarray(b["id"]) for b in batches])
        floats = np.concatenate([np.asarray(b["float_col"]) for b in batches])
        return ids, floats

    expected = {r["id"]: r["float_col"] for r in scalar_dataset.data}
    for capacity in (64, 10):  # >= dataset (drain-only) and < dataset (steady exchange)
        ids, floats = run(capacity)
        assert sorted(ids.tolist()) == sorted(expected)
        assert ids.tolist() != sorted(expected), "capacity=%d did not shuffle" % capacity
        for i, f in zip(ids, floats):  # columns stay row-aligned through the ring
            # float32 tolerance: device_put truncates float64 with jax x64 off,
            # exactly as the non-shuffled device path does
            assert abs(expected[int(i)] - float(f)) < 1e-5

    a, _ = run(10, seed=11)
    b, _ = run(10, seed=11)
    assert a.tolist() == b.tolist()  # deterministic in the seed
    c, _ = run(10, seed=12)
    assert a.tolist() != c.tolist()


def test_device_shuffle_rejects_host_columns(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url)  # string_col is host-only
    loader = DataLoader(reader, batch_size=5, device_shuffle_capacity=32)
    with loader, pytest.raises(ValueError, match="host-only"):
        for _ in loader:
            pass


def test_device_shuffle_requires_to_device(scalar_dataset):
    reader = make_batch_reader(scalar_dataset.url)
    with pytest.raises(ValueError, match="to_device"):
        DataLoader(reader, batch_size=5, device_shuffle_capacity=32, to_device=False)
    reader.stop()
    reader.join()


def test_multiprocess_inmem_guards(scalar_dataset, monkeypatch):
    """Review r3: multi-process InMemDataLoader must reject a replicated batch axis
    (divergent per-process shards would silently assemble as 'replicas') and any
    missing sharding/last_batch misconfig — before touching the reader."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from petastorm_tpu.loader import InMemDataLoader

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1)
    try:
        with pytest.raises(ValueError, match="requires a sharding"):
            InMemDataLoader(reader, batch_size=8)  # no sharding at all
        with pytest.raises(ValueError, match="drop"):
            InMemDataLoader(reader, batch_size=8, last_batch="partial",
                            sharding=NamedSharding(mesh, P("dp")))
        with pytest.raises(ValueError, match="replicated batch axis|spans processes"):
            InMemDataLoader(reader, batch_size=8,
                            sharding=NamedSharding(mesh, P(None)))
    finally:
        reader.stop()
        reader.join()


# -- the transfer thread's wait: transfers that read recycled buffers, never outputs -----

def _record_waits(monkeypatch):
    """Wrap ``jax.block_until_ready`` as the loader calls it; returns the list of
    the leaves of every call (the arrays themselves, kept alive)."""
    import jax

    waited = []
    real = jax.block_until_ready

    def recording(x):
        waited.extend(jax.tree_util.tree_leaves(x))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", recording)
    return waited


def _is_among(arr, arrays):
    return any(arr is a for a in arrays)


def test_h2d_wait_skips_stage2_outputs(tmp_path, monkeypatch):
    """A device-decode store with a staging slab: the wait covers the columns'
    ``h2d.put`` arrays (read from the slab) and no stage-2 output; every batch
    hands its one decoded field on unwaited."""
    pytest.importorskip("cv2")
    from petastorm_tpu.io import staging
    from test_common import create_test_jpeg_dataset

    monkeypatch.setattr(staging, "_alias_probe", False)  # a copying backend
    ds = create_test_jpeg_dataset("file://" + str(tmp_path / "ds"), num_rows=24)
    waited = _record_waits(monkeypatch)
    reader = make_batch_reader(ds.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False, workers_count=1)
    with DataLoader(reader, 8, staging=True) as loader:
        batches = list(loader)
    assert len(batches) == 3
    for batch in batches:
        assert not _is_among(batch["image_jpeg"], waited)
        assert _is_among(batch["id"], waited) and _is_among(batch["label"], waited)
    assert len(waited) == 2 * len(batches)  # the two put columns, nothing else
    snap = loader.stats.snapshot()
    assert snap["h2d_unwaited_outputs"] == snap["batches"] == 3
    assert snap["h2d_wait_s"] > 0


def test_h2d_wait_set_unchanged_for_tabular_store(scalar_dataset, monkeypatch):
    """A store with no device program waits on every column it puts, as before,
    and hands no output on unwaited."""
    import jax

    from petastorm_tpu.io import staging

    monkeypatch.setattr(staging, "_alias_probe", False)
    waited = _record_waits(monkeypatch)
    reader = make_batch_reader(scalar_dataset.url, num_epochs=1)
    with DataLoader(reader, 5, staging=True) as loader:
        batches = list(loader)
    put = [v for b in batches for v in b.values() if isinstance(v, jax.Array)]
    assert put and len(waited) == len(put)
    assert all(_is_among(a, waited) for a in put)
    assert loader.stats.snapshot()["h2d_unwaited_outputs"] == 0


def _leased_jpeg_batch(url, n):
    """The first ``n`` rows of a device-decode JPEG store as a leased batch whose
    stage-1 coefficients and quantization tables live in one ring slab (rows
    reference it as their row group's stacked buffers), the lease scribbling
    the slab on release as a recycling ring would; with owned copies of the
    same planes for a fresh decode."""
    from petastorm_tpu.io.lease import Lease, LeasedBatch
    from petastorm_tpu.ops.jpeg import JpegComponent, JpegPlanes

    with make_batch_reader(url, decode_on_device=True, num_epochs=1,
                           shuffle_row_groups=False, reader_pool_type="dummy") as r:
        item = next(iter(r))
    cols = item._asdict() if hasattr(item, "_asdict") else item
    planes = [p.detach() for p in list(cols["image_jpeg"])[:n]]
    ncomp = len(planes[0].components)
    shapes = [c.blocks.shape for c in planes[0].components]
    sizes = [n * int(np.prod(s)) * 2 for s in shapes] + [n * ncomp * 64 * 4]
    slab = bytearray(sum(sizes))
    offset, parents = 0, []
    for c, s in enumerate(shapes):
        parent = np.frombuffer(slab, np.int16, n * int(np.prod(s)), offset)
        parent = parent.reshape(n, -1, 64)
        parent[:] = np.stack([p.components[c].blocks.reshape(-1, 64) for p in planes])
        parents.append(parent)
        offset += sizes[c]
    qtabs = np.frombuffer(slab, np.int32, n * ncomp * 64, offset).reshape(n, ncomp, 64)
    qtabs[:] = [[c.qtable for c in p.components] for p in planes]
    leased = np.empty(n, dtype=object)
    parents = tuple(parents)
    for i, p in enumerate(planes):
        comps = [JpegComponent(parents[c][i].reshape(shapes[c]), qtabs[i, c],
                               pc.h_samp, pc.v_samp)
                 for c, pc in enumerate(p.components)]
        leased[i] = JpegPlanes(p.height, p.width, comps,
                               batch_ref=(parents, qtabs, i))
    recycled = []

    def recycle():
        slab[:] = b"\x7f" * len(slab)
        recycled.append(True)

    batch = LeasedBatch({"id": np.array(cols["id"][:n]), "image_jpeg": leased},
                        leases=[Lease(release_cb=recycle, kind="test_slab")])
    return batch, planes, recycled


@pytest.mark.parametrize("aliasing", [False, True])
def test_stage2_inputs_from_leased_buffers_are_guarded(tmp_path, monkeypatch, aliasing):
    """Stage-2 inputs that view a leased slab (the no-native packer slices the
    rows' stacked buffers): a copying backend waits on their transfers before
    the lease goes back, an aliasing one puts owned copies; either way the
    delivered images equal a fresh decode after the slab was recycled, and the
    stage-2 output is never waited on."""
    pytest.importorskip("cv2")
    from petastorm_tpu.io import staging
    from petastorm_tpu.io.lease import copy_census
    from petastorm_tpu.ops import native
    from test_common import create_test_jpeg_dataset

    ds = create_test_jpeg_dataset("file://" + str(tmp_path / "ds"), num_rows=24)
    n = 6
    batch, planes, recycled = _leased_jpeg_batch(ds.url, n)
    monkeypatch.setattr(native, "native_available", lambda: False)
    monkeypatch.setattr(staging, "_alias_probe", aliasing)
    waited = _record_waits(monkeypatch)
    reader = make_batch_reader(ds.url, decode_on_device=True, num_epochs=1)
    copied = copy_census().get("h2d_owned_copy", 0)
    with DataLoader(reader, n) as loader:
        arrays, _host = loader._transfer(batch, 0)
    assert recycled == [True]
    ncomp = len(planes[0].components)
    inputs = [a for a in waited if a.shape[0] == n and a.ndim >= 2]
    if aliasing:
        assert inputs == []  # owned copies need no wait
        assert copy_census()["h2d_owned_copy"] - copied == sum(
            n * c.blocks.size * 2 + n * 64 * 4 for c in planes[0].components)
    else:
        assert len(inputs) == 2 * ncomp  # each component's coefficients and qtables
    assert not _is_among(arrays["image_jpeg"], waited)
    assert loader.stats.h2d_unwaited_outputs == 1
    field = reader.schema.fields["image_jpeg"]
    fresh = field.codec.device_decode_batch(field, planes)
    np.testing.assert_array_equal(np.asarray(arrays["image_jpeg"]), np.asarray(fresh))
    np.testing.assert_array_equal(np.asarray(arrays["id"]), np.asarray(batch["id"]))
