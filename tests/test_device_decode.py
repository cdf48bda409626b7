"""End-to-end on-device JPEG decode: reader ships coefficient staging payloads, the
DataLoader finishes decode on device in one batched dispatch (SURVEY.md §8 hard part #1;
reference host hot spot: petastorm/codecs.py ~L200 cv2.imdecode)."""
import numpy as np
import pytest

pytest.importorskip("cv2")

from petastorm_tpu.loader import DataLoader  # noqa: E402
from petastorm_tpu.ngram import NGram  # noqa: E402
from petastorm_tpu.ops.jpeg import JpegPlanes  # noqa: E402
from petastorm_tpu.reader import make_batch_reader, make_reader  # noqa: E402
from test_common import JpegSchema, create_test_jpeg_dataset  # noqa: E402


@pytest.fixture(scope="module")
def jpeg_dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp("jpeg_ds")
    return create_test_jpeg_dataset("file://" + str(path / "ds"), num_rows=24)


def _host_decoded(dataset):
    """Expected images: the portable host path (cv2 decode of the stored bytes)."""
    field = JpegSchema.fields["image_jpeg"]
    out = {}
    for row in dataset.data:
        encoded = field.codec.encode(field, row["image_jpeg"])
        out[row["id"]] = field.codec.decode(field, encoded)
    return out


def test_make_reader_ships_staging_payloads(jpeg_dataset):
    with make_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                     shuffle_row_groups=False) as reader:
        assert reader.device_decode_fields == frozenset({"image_jpeg"})
        row = next(iter(reader))
        assert isinstance(row.image_jpeg, JpegPlanes)
        assert row.image_jpeg.height == 32 and row.image_jpeg.width == 48


def test_loader_device_decode_per_row_path(jpeg_dataset):
    expected = _host_decoded(jpeg_dataset)
    reader = make_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                         shuffle_row_groups=False)
    seen = 0
    with DataLoader(reader, batch_size=6) as loader:
        for batch in loader:
            imgs = np.asarray(batch["image_jpeg"])
            ids = np.asarray(batch["id"])
            assert imgs.dtype == np.uint8 and imgs.shape == (6, 32, 48, 3)
            for i, rid in enumerate(ids):
                ref = expected[int(rid)]
                diff = np.abs(imgs[i].astype(int) - ref.astype(int))
                assert diff.mean() < 2.0 and np.percentile(diff, 99) <= 12
                seen += 1
    assert seen == 24


def test_loader_device_decode_batch_path(jpeg_dataset):
    expected = _host_decoded(jpeg_dataset)
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    assert reader.device_decode_fields == frozenset({"image_jpeg"})
    seen = 0
    with DataLoader(reader, batch_size=8) as loader:
        for batch in loader:
            imgs = np.asarray(batch["image_jpeg"])
            ids = np.asarray(batch["id"])
            assert imgs.shape == (8, 32, 48, 3)
            for i, rid in enumerate(ids):
                ref = expected[int(rid)]
                diff = np.abs(imgs[i].astype(int) - ref.astype(int))
                assert diff.mean() < 2.0
                seen += 1
    assert seen == 24


def test_device_decode_sharded_batches(jpeg_dataset):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=8, sharding=sharding) as loader:
        batch = next(iter(loader))
        img = batch["image_jpeg"]
        assert img.shape == (8, 32, 48, 3)
        assert img.sharding.is_equivalent_to(
            NamedSharding(mesh, PartitionSpec("dp", None, None, None)), 4)


def test_spmd_decode_shards_across_devices(jpeg_dataset):
    """VERDICT r3 #2: with a batch sharding, stage 2 runs SPMD — the decoded batch's
    shards land on DISTINCT devices (one batch slice each, no single-chip decode then
    redistribute), and output is bit-identical to the single-device path."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from petastorm_tpu.ops.jpeg import decode_jpeg_batch

    with make_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                     shuffle_row_groups=False) as reader:
        planes = [row.image_jpeg for row in reader][:16]
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("b",))
    s = NamedSharding(mesh, PartitionSpec("b"))
    sharded = decode_jpeg_batch(planes, sharding=s)
    single = decode_jpeg_batch(planes)
    assert sharded.shape == (16, 32, 48, 3)
    # every device holds exactly one distinct 2-row shard — SPMD, not replicated
    assert len(sharded.sharding.device_set) == 8
    shard_devs = {sh.device for sh in sharded.addressable_shards}
    assert len(shard_devs) == 8
    for sh in sharded.addressable_shards:
        assert sh.data.shape == (2, 32, 48, 3)
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(single))
    # each shard equals an independent decode of its own slice: stage 2 was
    # shard-local (a cross-device gather/redistribute could not satisfy this
    # per-device without also matching the slice boundaries exactly)
    for sh in sharded.addressable_shards:
        lo = sh.index[0].start or 0
        per_slice = decode_jpeg_batch(planes[lo:lo + 2])
        np.testing.assert_array_equal(np.asarray(sh.data), np.asarray(per_slice))


def test_spmd_decode_indivisible_batch_falls_back(jpeg_dataset):
    """A batch that does not divide the shard count decodes single-device (correct,
    just unscaled) — never a crash or silent row drop."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from petastorm_tpu.ops.jpeg import decode_jpeg_batch

    with make_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                     shuffle_row_groups=False) as reader:
        planes = [row.image_jpeg for row in reader][:6]
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("b",))
    s = NamedSharding(mesh, PartitionSpec("b"))
    out = decode_jpeg_batch(planes, sharding=s)  # 6 % 8 != 0
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(decode_jpeg_batch(planes)))


def test_loader_spmd_decode_output_presharded(jpeg_dataset):
    """Through the DataLoader, the decode output the consumer sees is already sharded
    across the mesh AND the decode itself produced it that way (the codec receives the
    loader's sharding — no decode-on-one-chip-then-device_put)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from petastorm_tpu import codecs as codecs_mod

    seen_shardings = []
    orig = codecs_mod.CompressedImageCodec.device_decode_batch

    def spy(self, field, staged, resize_to=None, sharding=None):
        seen_shardings.append(sharding)
        return orig(self, field, staged, resize_to=resize_to, sharding=sharding)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    try:
        codecs_mod.CompressedImageCodec.device_decode_batch = spy
        with DataLoader(reader, batch_size=8, sharding=sharding) as loader:
            batch = next(iter(loader))
            img = batch["image_jpeg"]
            assert len(img.sharding.device_set) == 8
    finally:
        codecs_mod.CompressedImageCodec.device_decode_batch = orig
    assert seen_shardings and all(s is sharding for s in seen_shardings)


def test_decode_unsharded_fallback_counter_and_warning(jpeg_dataset, caplog):
    """An 8-way batch sharding with an undivisible batch makes staged decode fall
    back to a single device: the loader must count it in
    ``PipelineStats.decode_unsharded_batches`` and warn once BEFORE the layout
    error surfaces (VERDICT r4 #6 — on a pod host this fallback silently makes one
    chip decode for eight)."""
    import logging

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    loader = DataLoader(reader, batch_size=6, sharding=sharding)  # 6 % 8 != 0
    # the warning rides the structured degradation log (ISSUE 3)
    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.obs"):
        with loader:
            try:
                for _ in loader:
                    pass
            except Exception:  # noqa: BLE001 — 6 rows cannot device_put 8-way; the
                pass  # counter/warning must fire BEFORE that layout error  # graftlint: disable=GL-O002
    assert loader.stats.decode_unsharded_batches >= 1
    warnings = [r for r in caplog.records
                if "SINGLE device" in r.getMessage()]
    assert len(warnings) == 1  # warn-once contract


def test_device_decode_then_device_transform(jpeg_dataset):
    import jax.numpy as jnp

    def normalize(batch):
        out = dict(batch)
        out["image_jpeg"] = batch["image_jpeg"].astype(jnp.float32) / 255.0
        return out

    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=8, device_transform=normalize) as loader:
        batch = next(iter(loader))
        img = np.asarray(batch["image_jpeg"])
        assert img.dtype == np.float32
        assert 0.0 <= img.min() and img.max() <= 1.0


def test_decode_on_device_rejects_ngram(jpeg_dataset):
    fields = {0: ["id", "image_jpeg"], 1: ["id"]}
    ngram = NGram(fields=fields, delta_threshold=10, timestamp_field="id")
    with pytest.raises(ValueError, match="NGram"):
        make_reader(jpeg_dataset.url, schema_fields=ngram, decode_on_device=True)


def test_decode_on_device_noop_without_jpeg_fields(jpeg_dataset):
    with make_reader(jpeg_dataset.url, schema_fields=["id", "label"],
                     decode_on_device=True, num_epochs=1) as reader:
        assert reader.device_decode_fields == frozenset()
        row = next(iter(reader))
        assert isinstance(row.id, np.int64)


def test_progressive_decodes_through_device_stage():
    """Progressive JPEG now rides the two-stage path natively (round-2 native SOF2
    support): host_stage_decode yields planes, and the batched device stage agrees
    with the full-host decode within lossy tolerance."""
    import cv2

    field = JpegSchema.fields["image_jpeg"]
    codec = field.codec
    rng = np.random.RandomState(9)
    img = np.kron(rng.randint(0, 256, (8, 12)).astype(np.float32),
                  np.ones((4, 4), np.float32))
    img = np.stack([img, img, img], -1).astype(np.uint8)
    baseline = bytes(codec.encode(field, img))
    ok, prog = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90,
                                          cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
    assert ok
    staged = [codec.host_stage_decode(field, baseline),
              codec.host_stage_decode(field, prog.tobytes()),
              codec.host_stage_decode(field, baseline)]
    from petastorm_tpu.ops import native
    if native.native_available():
        assert isinstance(staged[1], JpegPlanes)
    out = np.asarray(codec.device_decode_batch(field, staged))
    assert out.shape == (3, 32, 48, 3)
    np.testing.assert_array_equal(out[0], out[2])
    ref = codec.decode(field, prog.tobytes())
    assert np.abs(out[1].astype(int) - ref.astype(int)).mean() < 3.0


def test_host_fallback_rows_merge_back_in_order():
    """device_decode_batch must merge host-decoded fallback rows (the shape the loader
    stages when a stream is undecodable natively) back at their original positions."""
    field = JpegSchema.fields["image_jpeg"]
    codec = field.codec
    rng = np.random.RandomState(10)
    img = np.kron(rng.randint(0, 256, (8, 12)).astype(np.float32),
                  np.ones((4, 4), np.float32))
    img = np.stack([img, img, img], -1).astype(np.uint8)
    baseline = bytes(codec.encode(field, img))
    planes = codec.host_stage_decode(field, baseline)
    fallback = codec.decode(field, baseline)  # ndarray staged row (host fallback)
    out = np.asarray(codec.device_decode_batch(field, [planes, fallback, planes]))
    assert out.shape == (3, 32, 48, 3)
    np.testing.assert_array_equal(out[0], out[2])
    assert np.abs(out[1].astype(int) - np.asarray(out[0]).astype(int)).mean() < 3.0


def test_to_device_false_still_delivers_decoded_images(jpeg_dataset):
    expected = _host_decoded(jpeg_dataset)
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=8, to_device=False) as loader:
        batch = next(iter(loader))
        assert isinstance(batch["image_jpeg"], np.ndarray)
        assert batch["image_jpeg"].dtype == np.uint8
        assert batch["image_jpeg"].shape == (8, 32, 48, 3)
        ref = expected[int(batch["id"][0])]
        assert np.abs(batch["image_jpeg"][0].astype(int) - ref.astype(int)).mean() < 2.0


def test_decode_on_device_rejects_host_transform(jpeg_dataset):
    from petastorm_tpu.transform import TransformSpec

    spec = TransformSpec(func=lambda r: r)
    with pytest.raises(ValueError, match="host transform_spec"):
        make_reader(jpeg_dataset.url, decode_on_device=True, transform_spec=spec)


def test_native_rejects_corrupt_category_codes():
    """Corrupt DHT streams (DC category > 11) must raise, not hit UB."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable")
    import cv2

    rng = np.random.RandomState(10)
    ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (16, 16, 3), dtype=np.uint8),
                           [cv2.IMWRITE_JPEG_QUALITY, 90])
    data = bytearray(enc.tobytes())
    # find the DC DHT (FFC4, tc=0) and poison EVERY symbol to 200 (> max category 11),
    # so whichever code the scan hits first carries an invalid magnitude category
    i = data.find(b"\xff\xc4")
    assert i > 0 and data[i + 4] >> 4 == 0
    total = sum(data[i + 5:i + 21])
    for j in range(total):
        data[i + 21 + j] = 200
    with pytest.raises(ValueError):
        native.jpeg_decode_image_native(bytes(data))


def test_cache_key_distinguishes_device_payloads():
    from petastorm_tpu.reader import _cache_key

    class Piece:
        path = "/p"
        row_group = 0

    host = _cache_key(Piece, JpegSchema, None, None, 0, 1, None)
    dev = _cache_key(Piece, JpegSchema, None, None, 0, 1, None,
                     frozenset({"image_jpeg"}))
    assert host != dev


def test_per_row_path_mixed_staged_and_fallback_rows(jpeg_dataset):
    """Per-row readers can interleave JpegPlanes staging payloads with host-fallback
    ndarrays (progressive streams); the loader's column packing must force object
    dtype so batching/concat survives the mix (review r2 finding)."""
    import cv2

    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.loader import DataLoader

    field = JpegSchema.fields["image_jpeg"]
    codec = field.codec
    assert isinstance(codec, CompressedImageCodec)
    rng = np.random.RandomState(12)
    img = np.kron(rng.randint(0, 256, (8, 12)).astype(np.float32),
                  np.ones((4, 4), np.float32))
    img = np.stack([img, img, img], -1).astype(np.uint8)
    baseline = bytes(codec.encode(field, img))
    ok, prog = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90,
                                          cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
    assert ok

    class FakeRow:
        def __init__(self, i, payload):
            self._d = {"id": np.int64(i), "image_jpeg": payload}

        def _asdict(self):
            return dict(self._d)

    class FakeReader:
        is_batched_reader = False
        device_decode_fields = frozenset({"image_jpeg"})
        schema = JpegSchema
        transform_spec = None

        def __iter__(self):
            for i in range(8):
                enc = prog.tobytes() if i % 3 == 1 else baseline
                yield FakeRow(i, codec.host_stage_decode(field, enc))

        def stop(self):
            pass

        def join(self):
            pass

    with DataLoader(FakeReader(), batch_size=4) as loader:
        batches = list(loader)
    assert len(batches) == 2
    ref = codec.decode(field, baseline)
    for b in batches:
        imgs = np.asarray(b["image_jpeg"])
        assert imgs.shape == (4, 32, 48, 3)
        for row in imgs:
            assert np.abs(row.astype(int) - ref.astype(int)).mean() < 3.0


def test_process_pool_spmd_decode_sharded(jpeg_dataset):
    """Process pool × SPMD stage-2 × batch sharding: staged payloads cross the IPC
    wire, decode fans out across the 8-device mesh, and the delivered global batch
    matches the sync-pool single-device path bit-for-bit."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))

    def collect(pool, shard):
        reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True,
                                   num_epochs=1, shuffle_row_groups=False,
                                   reader_pool_type=pool, workers_count=2)
        out = {}
        with DataLoader(reader, batch_size=8, sharding=shard) as loader:
            for batch in loader:
                img = batch["image_jpeg"]
                if shard is not None:
                    assert len(img.sharding.device_set) == 8
                arr = np.asarray(img)
                for j, rid in enumerate(np.asarray(batch["id"])):
                    out[int(rid)] = arr[j]
        return out

    got = collect("process", sharding)
    ref = collect("dummy", None)
    assert sorted(got) == sorted(ref) == list(range(24))
    for rid in got:
        np.testing.assert_array_equal(got[rid], ref[rid])


def test_process_pool_device_decode_wire(tmp_path):
    """decode_on_device over the process pool: staged payloads cross the IPC wire
    (JpegPlanes.__reduce__ ships one detached row, not its row group's buffers) and
    the finished images match the host-decode path."""
    from petastorm_tpu import types as ptypes
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.metadata import write_dataset
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.unischema import Unischema, UnischemaField

    rng = np.random.RandomState(0)
    schema = Unischema("S", [
        UnischemaField("id", np.int64, (), ScalarCodec(ptypes.LongType()), False),
        UnischemaField("image", np.uint8, (24, 24, 3),
                       CompressedImageCodec("jpeg", 92), False),
    ])
    url = "file://" + str(tmp_path / "ds")
    write_dataset(url, schema,
                  ({"id": i, "image": rng.randint(0, 256, (24, 24, 3), dtype=np.uint8)}
                   for i in range(12)), rows_per_file=12)

    def collect(**kwargs):
        reader = make_batch_reader(url, num_epochs=1, **kwargs)
        out = {}
        with DataLoader(reader, 4, to_device=False, last_batch="partial") as loader:
            for b in loader:
                for j, i in enumerate(np.asarray(b["id"])):
                    out[int(i)] = np.asarray(b["image"])[j]
        return out

    got = collect(reader_pool_type="process", workers_count=2, decode_on_device=True)
    ref = collect()
    assert len(got) == 12
    worst = max(np.abs(got[i].astype(int) - ref[i].astype(int)).mean()
                for i in range(12))
    assert worst < 2.5, worst


def test_inmem_loader_over_device_decode_reader(jpeg_dataset):
    """InMemDataLoader fills through the staged decode path: the resident store holds
    DECODED images and epochs serve them without re-decoding."""
    from petastorm_tpu.loader import InMemDataLoader

    expected = _host_decoded(jpeg_dataset)
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with InMemDataLoader(reader, batch_size=8, num_epochs=2, seed=5) as loader:
        seen = 0
        for batch in loader:
            imgs = np.asarray(batch["image_jpeg"])
            ids = np.asarray(batch["id"])
            assert imgs.dtype == np.uint8 and imgs.shape[1:] == (32, 48, 3)
            for i, rid in enumerate(ids):
                ref = expected[int(rid)]
                assert np.abs(imgs[i].astype(int) - ref.astype(int)).mean() < 2.0
                seen += 1
        assert seen == 48  # 24 rows x 2 epochs (drop policy, 24 % 8 == 0)


@pytest.fixture(scope="module")
def hive_jpeg_dataset(tmp_path_factory):
    """Hive-partitioned petastorm-tpu dataset with a JPEG codec column: the
    ``split`` column lives ONLY in the directory path (Spark partitionBy layout)."""
    import os

    import pyarrow as pa
    import pyarrow.fs as pafs
    import pyarrow.parquet as pq

    from petastorm_tpu import types as ptypes
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.metadata import write_petastorm_tpu_metadata
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema("HiveJpeg", [
        UnischemaField("id", np.int64, (), ScalarCodec(ptypes.LongType()), False),
        UnischemaField("image_jpeg", np.uint8, (32, 48, 3),
                       CompressedImageCodec("jpeg", quality=90), False),
        UnischemaField("split", np.str_, (), ScalarCodec(ptypes.StringType()), False),
    ])
    field = schema.fields["image_jpeg"]
    rng = np.random.RandomState(3)
    root = tmp_path_factory.mktemp("hive_jpeg")
    rows = []
    counts = {}
    rid = 0
    for split in ("train", "val"):
        d = root / ("split=%s" % split)
        os.makedirs(d, exist_ok=True)
        imgs = []
        ids = []
        for _ in range(8):
            base = rng.randint(0, 256, (8, 12)).astype(np.float32)
            img = np.kron(base, np.ones((4, 4), np.float32))
            img = np.stack([img, np.flipud(img), np.fliplr(img)], -1)
            img = img.clip(0, 255).astype(np.uint8)
            imgs.append(img)
            ids.append(rid)
            rows.append({"id": rid, "split": split, "image_jpeg": img})
            rid += 1
        enc = [bytes(field.codec.encode(field, im)) for im in imgs]
        pq.write_table(
            pa.table({"id": pa.array(ids, pa.int64()),
                      "image_jpeg": pa.array(enc, pa.binary())}),
            str(d / "part-0.parquet"), row_group_size=4)
        counts["split=%s/part-0.parquet" % split] = 2
    write_petastorm_tpu_metadata(pafs.LocalFileSystem(), str(root), schema, counts)
    return {"url": "file://" + str(root), "rows": rows, "field": field}


def test_device_decode_composes_with_hive_pruning(hive_jpeg_dataset):
    """Partition-filter pruning + partition-column materialization + two-stage device
    decode in ONE reader: the pruned directory is never decoded, the surviving rows
    arrive with decoded images and the directory-born column."""
    reader = make_batch_reader(hive_jpeg_dataset["url"], decode_on_device=True,
                               filters=[("split", "=", "val")], num_epochs=1,
                               shuffle_row_groups=False)
    assert reader._num_items == 2  # one file x 2 row groups survives pruning
    field = hive_jpeg_dataset["field"]
    expected = {r["id"]: field.codec.decode(field, field.codec.encode(field, r["image_jpeg"]))
                for r in hive_jpeg_dataset["rows"] if r["split"] == "val"}
    seen = {}
    with DataLoader(reader, batch_size=4, last_batch="partial") as loader:
        for batch in loader:
            assert all(s == "val" for s in np.asarray(batch["split"]))
            imgs = np.asarray(batch["image_jpeg"])
            for i, rid in enumerate(np.asarray(batch["id"])):
                seen[int(rid)] = imgs[i]
    assert set(seen) == set(expected)
    for rid, img in seen.items():
        assert np.abs(img.astype(int) - expected[rid].astype(int)).mean() < 2.0


def test_device_decode_checkpoint_resume(jpeg_dataset):
    """state_dict/load_state_dict across a staged-decode reader: the resumed read
    completes the epoch with decodable payloads and no row lost or replayed."""
    expected = _host_decoded(jpeg_dataset)
    with make_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                     shuffle_row_groups=False, reader_pool_type="dummy") as reader:
        it = iter(reader)
        head = []
        for _ in range(8):  # one full row group on this fixture
            head.append(next(it))
        state = reader.state_dict()
    head_ids = [int(r.id) for r in head]

    with make_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                     shuffle_row_groups=False, reader_pool_type="dummy") as reader2:
        reader2.load_state_dict(state)
        with DataLoader(reader2, batch_size=4, last_batch="partial") as loader:
            seen = {}
            for batch in loader:
                imgs = np.asarray(batch["image_jpeg"])
                for i, rid in enumerate(np.asarray(batch["id"])):
                    seen[int(rid)] = imgs[i]
    assert sorted(head_ids + list(seen)) == list(range(24))
    for rid, img in seen.items():
        assert np.abs(img.astype(int) - expected[rid].astype(int)).mean() < 2.0


def test_device_decode_composes_with_device_shuffle(jpeg_dataset):
    """decode_on_device + device_shuffle_capacity in one loader: decoded image
    batches ride the HBM exchange ring — every row still appears exactly once per
    epoch, images stay correct, and order decorrelates from the plan order."""
    expected = _host_decoded(jpeg_dataset)
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    seen = {}
    order = []
    with DataLoader(reader, batch_size=4, device_shuffle_capacity=12,
                    seed=13) as loader:
        for batch in loader:
            imgs = np.asarray(batch["image_jpeg"])
            for i, rid in enumerate(np.asarray(batch["id"])):
                seen[int(rid)] = imgs[i]
                order.append(int(rid))
    assert sorted(order) == list(range(24))  # exactly once through the ring
    assert order != sorted(order)  # and not plan order
    for rid, img in seen.items():
        assert np.abs(img.astype(int) - expected[rid].astype(int)).mean() < 2.0


# --------------------------------------------------- mixed-size stores (device resize)


def _mixed_size_store(tmp_path, sizes, quality=90):
    """Vanilla-parquet-with-metadata store whose JPEG rows have DIFFERENT sizes."""
    import pyarrow as pa
    import pyarrow.fs as pafs
    import pyarrow.parquet as pq

    from petastorm_tpu import types as ptypes
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.metadata import write_petastorm_tpu_metadata
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema("Mixed", [
        UnischemaField("id", np.int64, (), ScalarCodec(ptypes.LongType()), False),
        UnischemaField("image_jpeg", np.uint8, (None, None, 3),
                       CompressedImageCodec("jpeg", quality=quality), False),
    ])
    field = schema.fields["image_jpeg"]
    rng = np.random.RandomState(11)
    imgs = []
    enc = []
    for i, (h, w) in enumerate(sizes):
        base = rng.randint(0, 256, (max(2, h // 8), max(2, w // 8))).astype(np.float32)
        img = np.kron(base, np.ones((8, 8), np.float32))[:h, :w]
        img = np.stack([img, np.flipud(img), np.fliplr(img)], -1)
        img = img.clip(0, 255).astype(np.uint8)
        imgs.append(img)
        enc.append(bytes(field.codec.encode(field, img)))
    pq.write_table(
        pa.table({"id": pa.array(np.arange(len(sizes), dtype=np.int64)),
                  "image_jpeg": pa.array(enc, pa.binary())}),
        str(tmp_path / "part-0.parquet"), row_group_size=len(sizes))
    write_petastorm_tpu_metadata(pafs.LocalFileSystem(), str(tmp_path), schema,
                                 {"part-0.parquet": 1})
    return "file://" + str(tmp_path), imgs, field


def test_mixed_sizes_without_resize_raise(tmp_path):
    url, _, _ = _mixed_size_store(tmp_path, [(32, 48), (64, 40), (32, 48)])
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with pytest.raises(ValueError, match="device_decode_resize"):
        with DataLoader(reader, batch_size=3, last_batch="partial") as loader:
            list(loader)


def test_mixed_sizes_resize_composes_with_spmd_sharding(tmp_path):
    """SPMD stage-2 decode × device_decode_resize × batch sharding: a mixed-size
    store delivers one static shape sharded across the mesh, values matching the
    unsharded path (the resize consumes already-sharded decode output)."""
    import cv2  # noqa: F401 — store construction uses the jpeg codec
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    sizes = [(32, 48), (64, 40), (48, 48), (32, 48), (80, 56), (24, 24),
             (40, 40), (56, 32)]
    url, imgs, field = _mixed_size_store(tmp_path, sizes)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))

    def collect(shard):
        reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                                   shuffle_row_groups=False)
        got = {}
        with DataLoader(reader, batch_size=8, sharding=shard,
                        device_decode_resize=(32, 32)) as loader:
            for batch in loader:
                arr = batch["image_jpeg"]
                if shard is not None:
                    assert len(arr.sharding.device_set) == 8
                arr = np.asarray(arr)
                assert arr.shape[1:] == (32, 32, 3)
                for i, rid in enumerate(np.asarray(batch["id"])):
                    got[int(rid)] = arr[i]
        return got

    sharded, single = collect(sharding), collect(None)
    assert sorted(sharded) == sorted(single) == list(range(len(sizes)))
    for rid in sharded:
        np.testing.assert_array_equal(sharded[rid], single[rid])


def test_mixed_sizes_device_resize(tmp_path):
    """Mixed-size store rides the device path with one static output shape; values
    track cv2 decode + cv2.resize INTER_LINEAR (the host reference idiom)."""
    import cv2

    sizes = [(32, 48), (64, 40), (48, 48), (32, 48), (80, 56), (24, 24)]
    url, imgs, field = _mixed_size_store(tmp_path, sizes)
    target = (32, 32)
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    got = {}
    with DataLoader(reader, batch_size=3, last_batch="partial",
                    device_decode_resize=target) as loader:
        for batch in loader:
            arr = np.asarray(batch["image_jpeg"])
            assert arr.shape[1:] == (32, 32, 3) and arr.dtype == np.uint8
            for i, rid in enumerate(np.asarray(batch["id"])):
                got[int(rid)] = arr[i]
    assert len(got) == len(sizes)
    for rid, (h, w) in enumerate(sizes):
        stored = field.codec.decode(field, field.codec.encode(field, imgs[rid]))
        if (h, w) != target:
            ref = cv2.resize(stored, (target[1], target[0]),
                             interpolation=cv2.INTER_LINEAR)
        else:
            ref = stored
        diff = np.abs(got[rid].astype(int) - ref.astype(int))
        assert diff.mean() < 3.0, (rid, diff.mean())


def test_uniform_store_resize_noop_bitexact(jpeg_dataset):
    """resize target == stored size must not perturb output: bit-equal to the
    no-resize device path."""
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=8,
                    device_decode_resize=(32, 48)) as loader:
        with_resize = {int(r): np.asarray(b["image_jpeg"])[i]
                       for b in loader for i, r in enumerate(np.asarray(b["id"]))}
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=8) as loader:
        plain = {int(r): np.asarray(b["image_jpeg"])[i]
                 for b in loader for i, r in enumerate(np.asarray(b["id"]))}
    assert set(with_resize) == set(plain)
    for rid in plain:
        np.testing.assert_array_equal(with_resize[rid], plain[rid])


def test_mixed_sizes_host_fallback_rows_resized(tmp_path, monkeypatch):
    """Rows the native stage rejects (host cv2 fallback) resize on host and merge at
    their positions alongside device-resized rows."""
    url, imgs, field = _mixed_size_store(tmp_path, [(32, 48), (64, 40), (48, 32)])
    from petastorm_tpu.ops import jpeg as J

    real = J.entropy_decode_jpeg_batch

    def partial_batch(blobs):
        out = real(blobs)
        if len(out) > 1:
            out[1] = None  # force one row down the host fallback path
        return out

    def refuse_fast(data):
        raise ValueError("forced: no native per-image decode either")

    # batch rejects row 1 AND the per-image native path refuses -> a genuine
    # cv2-decoded ndarray lands in the staged column next to JpegPlanes rows
    monkeypatch.setattr(J, "entropy_decode_jpeg_batch", partial_batch)
    monkeypatch.setattr(J, "entropy_decode_jpeg_fast", refuse_fast)
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=3, device_decode_resize=(32, 32)) as loader:
        (batch,) = list(loader)
    arr = np.asarray(batch["image_jpeg"])
    assert arr.shape == (3, 32, 32, 3)
    import cv2

    for i in range(3):
        stored = field.codec.decode(field, field.codec.encode(field, imgs[i]))
        ref = cv2.resize(stored, (32, 32), interpolation=cv2.INTER_LINEAR)
        assert np.abs(arr[i].astype(int) - ref.astype(int)).mean() < 3.0, i


def test_device_decode_resize_validated_at_construction(jpeg_dataset):
    reader = make_batch_reader(jpeg_dataset.url, decode_on_device=True, num_epochs=1)
    try:
        with pytest.raises(ValueError, match="image_jpeg"):
            DataLoader(reader, batch_size=4,
                       device_decode_resize={"imaeg_jpeg": (32, 32)})  # misspelled
        with pytest.raises(ValueError, match="pair"):
            DataLoader(reader, batch_size=4, device_decode_resize=32)
        with pytest.raises(ValueError, match="positive"):
            DataLoader(reader, batch_size=4, device_decode_resize=(0, 32))
    finally:
        reader.stop()
        reader.join()


def test_device_decode_resize_requires_decode_fields(jpeg_dataset):
    """A resize target against a reader with no device-decoded fields must fail at
    construction, not silently no-op."""
    reader = make_batch_reader(jpeg_dataset.url, num_epochs=1)  # host decode
    try:
        with pytest.raises(ValueError, match="decode_on_device"):
            DataLoader(reader, batch_size=4, device_decode_resize=(32, 32))
    finally:
        reader.stop()
        reader.join()


def test_inmem_loader_mixed_sizes_with_resize(tmp_path):
    """InMemDataLoader fills a mixed-size store through the staged decode + resize:
    the HBM-resident store holds one static shape, epochs serve it directly."""
    from petastorm_tpu.loader import InMemDataLoader

    sizes = [(32, 48), (64, 40), (48, 48), (24, 24)] * 2
    url, _, _ = _mixed_size_store(tmp_path, sizes)
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with InMemDataLoader(reader, batch_size=4, num_epochs=2, seed=7,
                         device_decode_resize=(32, 32)) as loader:
        seen = 0
        for batch in loader:
            arr = np.asarray(batch["image_jpeg"])
            assert arr.shape == (4, 32, 32, 3) and arr.dtype == np.uint8
            seen += len(arr)
    assert seen == 2 * len(sizes)


def test_weighted_sampling_device_decode_with_resize(tmp_path):
    """WeightedSamplingReader over two mixed-size stores passes the staged-decode
    fields through; the loader's resize gives the mixed stream one static shape."""
    from petastorm_tpu import WeightedSamplingReader

    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    url_a, _, _ = _mixed_size_store(tmp_path / "a", [(32, 48), (64, 40)] * 2)
    url_b, _, _ = _mixed_size_store(tmp_path / "b", [(24, 24), (48, 32)] * 2)
    r1 = make_batch_reader(url_a, decode_on_device=True, num_epochs=1,
                           shuffle_row_groups=False)
    r2 = make_batch_reader(url_b, decode_on_device=True, num_epochs=1,
                           shuffle_row_groups=False)
    mixed = WeightedSamplingReader([r1, r2], [0.5, 0.5], seed=4)
    assert mixed.device_decode_fields == frozenset({"image_jpeg"})
    seen = 0
    with DataLoader(mixed, batch_size=4, last_batch="partial",
                    device_decode_resize=(32, 32)) as loader:
        for batch in loader:
            arr = np.asarray(batch["image_jpeg"])
            assert arr.shape[1:] == (32, 32, 3)
            seen += len(arr)
    assert seen == 8


@pytest.mark.parametrize("mixed", [False, True], ids=["row_group", "per_image"])
def test_stage1_counters_fold_into_pipeline_stats(jpeg_dataset, tmp_path, mixed):
    """The reader pool's stage-1 decode counts into the loader's ``PipelineStats``
    through the process-wide totals: every staged image once, the decoding threads'
    CPU time, the Huffman symbols; ``reset()`` clears them. A dummy pool decodes
    inside the loader's reads, so nothing is staged before the loader starts."""
    if mixed:  # several sizes in a row group: stage 1 goes image by image
        url, _, _ = _mixed_size_store(tmp_path, [(32, 48), (64, 40), (48, 48)] * 4)
        kwargs, rows = {"device_decode_resize": (32, 32)}, 12
    else:  # one layout: the row group's one batched call
        url, kwargs, rows = jpeg_dataset.url, {}, 24
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False, reader_pool_type="dummy")
    with DataLoader(reader, batch_size=4, **kwargs) as loader:
        delivered = sum(len(np.asarray(b["id"])) for b in loader)
    stats = loader.stats
    assert delivered == rows and stats.stage1_images == rows
    assert stats.stage1_ns > 0
    assert stats.stage1_symbols >= stats.stage1_fast_symbols > 0
    assert stats.snapshot()["stage1_images"] == rows
    stats.reset()
    assert stats.stage1_images == stats.stage1_ns == stats.stage1_symbols == 0
