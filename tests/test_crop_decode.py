"""Mixed-size JPEG decode in canvas buckets with a crop, resize and flip on the
device, against a plain reference: cv2's decode, a numpy crop, ``cv2.resize``
INTER_LINEAR and a left-right flip."""
import math

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from petastorm_tpu.ops import crop as C  # noqa: E402
from petastorm_tpu.ops import jpeg as J  # noqa: E402
from petastorm_tpu.ops import native  # noqa: E402

#: canvases an eighth of the default's, so the CPU decodes small programs
SMALL_EDGES = (64, 128, 256)


def _image(rng, h, w):
    base = rng.integers(0, 256, (max(2, h // 8), max(2, w // 8), 3)).astype(np.float32)
    img = cv2.resize(base, (w, h), interpolation=cv2.INTER_LINEAR)
    img += rng.normal(0, 12, img.shape)
    return img.clip(0, 255).astype(np.uint8)


def _blobs(sizes, seed=0, quality=90):
    rng = np.random.default_rng(seed)
    return [cv2.imencode(".jpg", _image(rng, h, w), [cv2.IMWRITE_JPEG_QUALITY, quality])[1]
            .tobytes() for h, w in sizes]


def _reference(blob, box, size):
    """cv2 decode, a numpy crop, cv2.resize INTER_LINEAR, a flip."""
    img = cv2.imdecode(np.frombuffer(blob, np.uint8), cv2.IMREAD_UNCHANGED)
    y0, x0, h, w, flip = (int(v) for v in box)
    out = cv2.resize(np.ascontiguousarray(img[y0:y0 + h, x0:x0 + w]), (size[1], size[0]),
                     interpolation=cv2.INTER_LINEAR)
    return out[:, ::-1] if flip else out


def _assert_close(got, want, what):
    gap = np.abs(got.astype(np.int16) - want.astype(np.int16))
    assert gap.mean() < 1.0 and gap.max() <= 8, (what, gap.mean(), gap.max())


def _stats():
    from petastorm_tpu.loader import PipelineStats

    return PipelineStats()


@pytest.fixture
def small_canvases(monkeypatch):
    monkeypatch.setattr(J, "CANVAS_EDGES", SMALL_EDGES)


@pytest.fixture
def counted():
    """A ``PipelineStats`` receiving the decode's counters and phases."""
    from petastorm_tpu.trace import set_phase_sink

    stats = _stats()
    set_phase_sink(stats)
    yield stats
    set_phase_sink(None)


def _planes(blobs):
    return [J.entropy_decode_jpeg_fast(b) for b in blobs]


# -- the box draw ----------------------------------------------------------------------


def _torchvision_boxes(sizes, policy, rng):
    """torchvision's ``RandomResizedCrop.get_params`` row by row, reading the same
    draws :func:`crop.draw_boxes` consumes in the same order."""
    n = len(sizes)
    area_u = rng.uniform(policy.scale[0], policy.scale[1], (n, C.ATTEMPTS))
    logr_u = rng.uniform(math.log(policy.ratio[0]), math.log(policy.ratio[1]),
                         (n, C.ATTEMPTS))
    pos = rng.random((n, 2))
    flips = rng.random(n) < policy.flip
    out = []
    for i, (height, width) in enumerate(sizes):
        area = height * width
        for a in range(C.ATTEMPTS):
            target_area = area * area_u[i, a]
            aspect_ratio = math.exp(logr_u[i, a])
            w = int(round(math.sqrt(target_area * aspect_ratio)))
            h = int(round(math.sqrt(target_area / aspect_ratio)))
            if 0 < w <= width and 0 < h <= height:
                top = int(pos[i, 0] * (height - h + 1))
                left = int(pos[i, 1] * (width - w + 1))
                break
        else:
            in_ratio = width / height
            if in_ratio < min(policy.ratio):
                w, h = width, int(round(width / min(policy.ratio)))
            elif in_ratio > max(policy.ratio):
                h, w = height, int(round(height * max(policy.ratio)))
            else:
                w, h = width, height
            top, left = (height - h) // 2, (width - w) // 2
        out.append((top, left, h, w, int(flips[i])))
    return np.array(out, np.int32)


@pytest.mark.parametrize("scale,ratio", [((0.08, 1.0), (0.75, 4 / 3)),
                                         ((0.9, 1.0), (3.0, 4.0)),
                                         ((0.5, 0.5), (1.0, 1.0))])
def test_box_draw_equals_torchvision_get_params(scale, ratio):
    """Vectorised draws equal a plain loop of torchvision's algorithm for the same
    Philox key: first fitting attempt, else the center crop clamped to ``ratio``."""
    rng = np.random.default_rng(4)
    sizes = [(int(h), int(w)) for h, w in rng.integers(1, 900, (300, 2))] \
        + [(1, 1), (1, 500), (500, 1), (375, 500)]
    policy = C.parse_policy({"size": [224, 224], "crop": "random_resized",
                             "scale": list(scale), "ratio": list(ratio), "flip": 0.5})
    got = C.draw_boxes(sizes, policy, C.generator(2 ** 40 + 7, 12))
    want = _torchvision_boxes(sizes, policy, C.generator(2 ** 40 + 7, 12))
    np.testing.assert_array_equal(got, want)
    # another batch number, another draw
    other = C.draw_boxes(sizes, policy, C.generator(2 ** 40 + 7, 13))
    assert not np.array_equal(got, other)
    # every box in its image
    hw = np.array(sizes)
    assert (got[:, 0] >= 0).all() and (got[:, 0] + got[:, 2] <= hw[:, 0]).all()
    assert (got[:, 1] >= 0).all() and (got[:, 1] + got[:, 3] <= hw[:, 1]).all()


def test_policy_parse_and_reject():
    assert C.parse_policy((224, 200)) == C.CropPolicy((224, 200))
    assert not C.parse_policy((4, 4)).draws
    assert C.parse_policy({"size": [8, 8], "flip": 0.5}).draws
    p = C.parse_policy({"size": [224, 224], "crop": "random_resized"})
    assert p.scale == (0.08, 1.0) and p.ratio == (0.75, 4 / 3) and p.flip == 0
    for bad in ({"size": [8, 8], "crop": "center"}, {"size": [8, 8], "flip": 2},
                {"size": [8, 8], "scale": [0.5, 0.1]}, {"crop": "whole"},
                {"size": [8, 8], "sise": 1}, {"size": [8]}, (0, 4), 7):
        with pytest.raises(ValueError):
            C.parse_policy(bad)


# -- the canvas decode -----------------------------------------------------------------


def test_mixed_sizes_crop_resize_flip_match_reference(small_canvases, counted):
    """Sizes from 17x23 to 300x200 in one batch, edges that are no MCU multiple,
    random boxes and flips: each image matches the plain reference; the tail images
    beyond the largest canvas that reach stage 2 decode in their own sizes."""
    sizes = [(17, 23), (300, 200), (64, 64), (48, 130), (200, 250), (31, 31), (100, 7),
             (63, 65), (129, 33), (260, 90)]
    blobs = _blobs(sizes)
    planes = _planes(blobs)
    assert [i for i, p in enumerate(planes) if not J.canvas_holds(p)] == [1, 9]
    policy = C.parse_policy({"size": [40, 36], "crop": "random_resized", "flip": 0.5})
    boxes = C.draw_boxes(sizes, policy, C.generator(5, 0))
    out = np.asarray(J.decode_jpeg_crop_batch(planes, boxes, (40, 36)))
    assert out.shape == (len(sizes), 40, 36, 3) and out.dtype == np.uint8
    for i in range(len(sizes)):
        _assert_close(out[i], _reference(blobs[i], boxes[i], (40, 36)), sizes[i])
    assert counted.decode_batches == 1
    # the 64, 128 and 256 canvases, then 304x200 and 272x96 (300x200, 260x90)
    assert counted.decode_launches == 5
    real = sum(c.blocks.shape[0] * c.blocks.shape[1]
               for p in planes for c in p.components)
    assert counted.decode_real_blocks == real < counted.decode_canvas_blocks


def test_edge_boxes_one_pixel_boxes_and_fallback(small_canvases):
    """Boxes flush with each edge, 1-px boxes (the output one colour, as cv2's) and
    the center-crop fallback, flipped and not."""
    sizes = [(50, 40)] * 6 + [(20, 90)] * 2
    blobs = _blobs(sizes, seed=3)
    planes = _planes(blobs)
    ratio = (0.75, 4 / 3)
    fb = [C.draw_boxes([s], C.parse_policy({"size": [8, 8], "crop": "random_resized",
                                           "scale": [2.0 / 3, 1.0], "ratio": list(ratio)}),
                       C.generator(0, 0))[0] for s in sizes[-2:]]
    boxes = np.array([(0, 0, 50, 40, 0), (0, 0, 50, 40, 1), (49, 39, 1, 1, 0),
                      (0, 39, 50, 1, 1), (49, 0, 1, 40, 0), (10, 0, 30, 40, 1),
                      tuple(fb[0]), tuple(fb[1][:4]) + (1,)], np.int32)
    assert tuple(boxes[6][:4]) == (0, 31, 20, 27)  # aspect 4.5: cut to 4/3, centred
    out = np.asarray(J.decode_jpeg_crop_batch(planes, boxes, (24, 28)))
    for i in range(len(sizes)):
        _assert_close(out[i], _reference(blobs[i], boxes[i], (24, 28)), boxes[i])


def test_launches_bounded_by_buckets_and_no_compile_for_new_mix(small_canvases, counted):
    """A batch of N distinct sizes launches one program a bucket present, and a second
    batch of other sizes in the same buckets compiles nothing new."""
    from jax import monitoring

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_k: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    size = (16, 16)
    first = [(40, 50), (60, 33), (100, 70), (17, 23), (50, 61), (130, 200), (22, 22),
             (64, 48)]
    second = [(41, 52), (59, 30), (120, 90), (18, 25), (45, 45), (170, 140), (24, 28),
              (62, 46)]
    # the second batch's coarser quantization keeps its coefficients inside the
    # first's narrowing tiers, so only the size mix differs
    for i, (sizes, quality) in enumerate(((first, 95), (second, 75))):
        planes = _planes(_blobs(sizes, seed=10 + i, quality=quality))
        boxes = C.whole_boxes(sizes)
        launches = counted.decode_launches
        before = len(compiles)
        out = J.decode_jpeg_crop_batch(planes, boxes, size)
        np.asarray(out)
        assert out.shape == (8, 16, 16, 3)
        assert counted.decode_launches - launches == 3  # 64, 128, 256: 8 sizes
        if i:
            assert len(compiles) == before, compiles[before:]


def test_single_layout_batch_decodes_at_its_own_size(counted):
    """A batch of one layout decodes in its own size, not a default canvas: one
    launch, and no padding but the rows the bucket pads to."""
    sizes = [(37, 45)] * 5
    blobs = _blobs(sizes, seed=7)
    planes = _planes(blobs)
    boxes = C.draw_boxes(sizes, C.parse_policy({"size": [20, 20], "crop": "random_resized",
                                                "flip": 0.5}), C.generator(1, 1))
    out = np.asarray(J.decode_jpeg_crop_batch(planes, boxes, (20, 20)))
    assert counted.decode_launches == 1
    # 4:2:0 rows pad to 8 (the fewest a bucket pads to); their own blocks only
    per_image = sum(c.blocks.shape[0] * c.blocks.shape[1] for c in planes[0].components)
    assert counted.decode_real_blocks == 5 * per_image
    assert counted.decode_canvas_blocks == 8 * per_image
    for i in range(5):
        _assert_close(out[i], _reference(blobs[i], boxes[i], (20, 20)), i)


def test_grayscale_field_and_gray_rows(small_canvases):
    """Grayscale JPEGs beside color ones (two samplings: two buckets); a grayscale
    field delivers the one plane."""
    rng = np.random.default_rng(2)
    gray = [cv2.imencode(".jpg", _image(rng, 30, 44)[..., 0])[1].tobytes(),
            cv2.imencode(".jpg", _image(rng, 52, 20)[..., 0])[1].tobytes()]
    color = _blobs([(40, 40)], seed=8)
    blobs = [gray[0], color[0], gray[1]]
    planes = _planes(blobs)
    boxes = C.whole_boxes([(p.height, p.width) for p in planes])
    boxes[0, 4] = 1
    out = np.asarray(J.decode_jpeg_crop_batch(planes, boxes, (16, 16)))
    for i, b in enumerate(blobs):
        ref = _reference(b, boxes[i], (16, 16))
        _assert_close(out[i], ref if ref.ndim == 3 else np.stack([ref] * 3, -1), i)
    planes = [planes[0], planes[2]]
    out = np.asarray(J.decode_jpeg_crop_batch(planes, boxes[[0, 2]], (16, 16),
                                              grayscale=True))
    assert out.shape == (2, 16, 16)
    for j, i in enumerate((0, 2)):
        _assert_close(out[j], _reference(blobs[i], boxes[i], (16, 16)), i)


def test_tail_image_of_a_mixed_row_group_decodes_on_the_host(small_canvases):
    """Stage 1 of a row group of several sizes sends an image no canvas holds to
    cv2 (the host fallback); a row group of its one size keeps it on the device
    path."""
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.unischema import UnischemaField

    codec = CompressedImageCodec("jpeg", quality=90)
    field = UnischemaField("image", np.uint8, (None, None, 3), codec, False)
    blobs = _blobs([(40, 50), (300, 200), (60, 33)], seed=4)
    out = codec.host_stage_decode_batch(field, blobs)
    assert isinstance(out[0], J.JpegPlanes) and isinstance(out[2], J.JpegPlanes)
    assert isinstance(out[1], np.ndarray)
    np.testing.assert_array_equal(
        out[1], cv2.imdecode(np.frombuffer(blobs[1], np.uint8), cv2.IMREAD_UNCHANGED))
    alone = codec.host_stage_decode_batch(field, [blobs[1]] * 2)
    assert all(isinstance(p, J.JpegPlanes) for p in alone)


def test_loader_crops_a_host_decoded_tail_image(tmp_path, small_canvases):
    """A tail image past the largest canvas, in a row group of several sizes,
    decodes on the host and is cropped there by its box (``decode_host_rows``)."""
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    sizes = [(40, 60), (300, 200), (100, 70), (64, 64)]
    url, blobs = _store(tmp_path, sizes)
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False, seed=3)
    with DataLoader(reader, batch_size=4, device_decode_resize={"image": POLICY}) as loader:
        (batch,) = list(loader)
        stats = loader.stats
    img, box = np.asarray(batch["image"]), np.asarray(batch["image_box"])
    for i, rid in enumerate(np.asarray(batch["id"])):
        _assert_close(img[i], _reference(blobs[rid], box[i], (48, 40)), rid)
    assert stats.decode_host_rows == 1 and stats.crop_boxes == 4
    assert stats.decode_launches == 2  # the 64 and 128 canvases


def _with_app1_thumbnail(blob, thumb):
    """``blob`` with an APP1 segment that carries another JPEG (an EXIF thumbnail's
    frame header comes before the image's own)."""
    payload = b"Exif\x00\x00" + thumb
    return blob[:2] + b"\xff\xe1" + (len(payload) + 2).to_bytes(2, "big") + payload \
        + blob[2:]


@pytest.mark.skipif(not native.native_available(), reason="native decoder unavailable")
def test_jpeg_size_reads_the_frame_header():
    """The header walk gives the native parser's size for baseline and progressive
    streams, skips a thumbnail's frame inside APP1, and gives None without a
    frame header."""
    rng = np.random.default_rng(5)
    baseline = _blobs([(17, 23), (300, 200)], seed=5)
    progressive = cv2.imencode(".jpg", _image(rng, 41, 66),
                               [cv2.IMWRITE_JPEG_PROGRESSIVE, 1])[1].tobytes()
    exif = _with_app1_thumbnail(baseline[1], baseline[0])
    assert cv2.imdecode(np.frombuffer(exif, np.uint8), cv2.IMREAD_UNCHANGED).shape \
        == (300, 200, 3)
    for blob in baseline + [progressive, exif]:
        assert J.jpeg_size(blob) == native.jpeg_parse_layout_native(blob)[:2]
    assert J.jpeg_size(progressive) == (41, 66)
    assert J.jpeg_size(b"\x89PNG\r\n\x1a\n") is None
    assert J.jpeg_size(baseline[0][:20]) is None
    assert J.canvas_holds((2048, 1536)) and not J.canvas_holds((1536, 2049))


def test_stage_images_serves_the_oldest_call_first():
    """Two calls at once: the second call's thread helps with the first call's
    items before its own, so the first call returns first; results keep their
    order."""
    import threading
    import time

    first_started = threading.Event()
    ran_first = set()
    returned = []

    def work(tag):
        def fn(x):
            if tag == "a":
                ran_first.add(threading.get_ident())
                first_started.set()
            time.sleep(0.01)
            return (tag, x)

        out = J.stage_images(fn, list(range(8)))
        returned.append(tag)
        assert out == [(tag, x) for x in range(8)]

    a = threading.Thread(target=work, args=("a",))
    a.start()
    first_started.wait(5)
    b = threading.Thread(target=work, args=("b",))
    b.start()
    a.join(10)
    b.join(10)
    assert returned == ["a", "b"]
    assert len(ran_first) == 2  # b's thread ran some of a's items


def test_stage_images_raises_on_the_callers_thread():
    with pytest.raises(ZeroDivisionError):
        J.stage_images(lambda x: 1 // x, [1, 0, 2])
    assert not J._STAGE_TASKS
    assert J.stage_images(lambda x: x, []) == []


@pytest.mark.skipif(not native.native_available(), reason="native decoder unavailable")
def test_row_group_of_one_size_takes_the_batched_stage1():
    """Headers of one size: one native call over the row group (rows view its
    stacked buffers); of several sizes: image by image, each row its own."""
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.unischema import UnischemaField

    codec = CompressedImageCodec("jpeg", quality=90)
    field = UnischemaField("image", np.uint8, (None, None, 3), codec, False)
    one = codec.host_stage_decode_batch(field, _blobs([(40, 56)] * 3, seed=6))
    assert all(p.batch_ref is not None for p in one)
    mixed_blobs = _blobs([(40, 56), (33, 17), (40, 56)], seed=6)
    mixed = codec.host_stage_decode_batch(field, mixed_blobs)
    assert all(p.batch_ref is None for p in mixed)
    for p, blob in zip(mixed, mixed_blobs):
        want = J.entropy_decode_jpeg_fast(blob)
        for got_c, want_c in zip(p.components, want.components):
            np.testing.assert_array_equal(got_c.blocks, want_c.blocks)


@pytest.mark.skipif(not native.native_available(), reason="native decoder unavailable")
def test_per_image_decode_carries_its_profile():
    """A per-image native decode carries the spectral profile and kmax a row-group
    decode would, so mixed-size rows still ship narrowed."""
    (blob,) = _blobs([(40, 56)], seed=9)
    p = J.entropy_decode_jpeg_fast(blob)
    assert p.specmax.shape == (3, 64)
    for c, comp in enumerate(p.components):
        want = native.jpeg_specmax_native(comp.blocks.reshape(1, -1, 64))
        np.testing.assert_array_equal(p.specmax[c], want)
        assert p.kmax[c] == (np.flatnonzero(want).max() if want.any() else 0)


# -- through the loader ----------------------------------------------------------------


def _store(tmp_path, sizes, seed=0):
    import pyarrow as pa
    import pyarrow.fs as pafs
    import pyarrow.parquet as pq

    from petastorm_tpu import types as ptypes
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.metadata import write_petastorm_tpu_metadata
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema("Raw", [
        UnischemaField("id", np.int64, (), ScalarCodec(ptypes.LongType()), False),
        UnischemaField("image", np.uint8, (None, None, 3),
                       CompressedImageCodec("jpeg", quality=90), False),
    ])
    blobs = _blobs(sizes, seed=seed)
    pq.write_table(pa.table({"id": pa.array(np.arange(len(sizes), dtype=np.int64)),
                             "image": pa.array(blobs, pa.binary())}),
                   str(tmp_path / "part-0.parquet"), row_group_size=len(sizes))
    write_petastorm_tpu_metadata(pafs.LocalFileSystem(), str(tmp_path), schema,
                                 {"part-0.parquet": 1})
    return "file://" + str(tmp_path), blobs


POLICY = {"size": [48, 40], "crop": "random_resized", "scale": [0.08, 1.0],
          "ratio": [0.75, 4 / 3], "flip": 0.5}


def test_loader_delivers_cropped_images_and_boxes(tmp_path):
    """``make_batch_reader(decode_on_device=True)`` → ``DataLoader(device_decode_resize=
    {"image": policy})`` at the default canvases: images match the reference at the
    delivered ``image_box``, boxes are drawn anew every epoch, and the counters and
    the ``decode.crop`` phase reach ``PipelineStats``."""
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    sizes = [(17, 23), (300, 200), (75, 100), (120, 64), (33, 300), (500, 375)]
    url, blobs = _store(tmp_path, sizes)
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=2,
                               shuffle_row_groups=False, seed=11)
    seen = {}
    with DataLoader(reader, batch_size=3, device_decode_resize={"image": POLICY}) as loader:
        for batch in loader:
            img, box = np.asarray(batch["image"]), np.asarray(batch["image_box"])
            assert img.shape == (3, 48, 40, 3) and box.shape == (3, 5)
            assert box.dtype == np.int32
            for i, rid in enumerate(np.asarray(batch["id"])):
                _assert_close(img[i], _reference(blobs[rid], box[i], (48, 40)), rid)
                seen.setdefault(int(rid), []).append(tuple(box[i]))
        stats = loader.stats
    assert sorted(seen) == list(range(len(sizes)))
    assert all(len(b) == 2 for b in seen.values())
    # 33x300 (aspect 9) always takes the center-crop fallback; the rest draw anew
    assert [b[:4] for b in seen[4]] == [(0, 128, 33, 44)] * 2
    assert all(b[0] != b[1] for rid, b in seen.items() if rid != 4)
    assert stats.crop_boxes == 12 and stats.decode_batches == 4
    assert stats.decode_launches == 4  # every image in the 512 canvas
    assert stats.decode_canvas_blocks > stats.decode_real_blocks > 0


def test_loader_boxes_follow_the_seed(tmp_path):
    """The same seed draws the same boxes; the loader's seed overrides the
    reader's."""
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    url, _ = _store(tmp_path, [(40, 60), (70, 30), (50, 50), (64, 64)])

    def boxes(policy, seed, loader_seed=None):
        reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                                   shuffle_row_groups=False, seed=seed)
        with DataLoader(reader, batch_size=4, device_decode_resize=policy,
                        seed=loader_seed) as loader:
            return np.concatenate([np.asarray(b["image_box"]) for b in loader])

    a = boxes({"image": POLICY}, 1)
    np.testing.assert_array_equal(a, boxes({"image": POLICY}, 1))
    assert not np.array_equal(a, boxes({"image": POLICY}, 2))
    np.testing.assert_array_equal(boxes(POLICY, 1, loader_seed=99),
                                  boxes(POLICY, 2, loader_seed=99))


def test_loader_rejects_a_bad_policy_at_construction(tmp_path):
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    url, _ = _store(tmp_path, [(40, 60), (70, 30)])
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1)
    try:
        with pytest.raises(ValueError, match="crop"):
            DataLoader(reader, batch_size=2,
                       device_decode_resize={"image": dict(POLICY, crop="center")})
        with pytest.raises(ValueError, match="flip"):
            DataLoader(reader, batch_size=2, device_decode_resize=dict(POLICY, flip=-1))
    finally:
        reader.stop()
        reader.join()


def test_whole_image_resize_needs_no_box(tmp_path):
    """An ``(h, w)`` pair is the whole-image policy: no box column, no draw, and the
    images match cv2.resize of the whole decode."""
    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    sizes = [(40, 60), (70, 30), (50, 50), (64, 64)]
    url, blobs = _store(tmp_path, sizes)
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with DataLoader(reader, batch_size=4, device_decode_resize=(32, 32)) as loader:
        (batch,) = list(loader)
        assert loader.stats.crop_boxes == 0
    assert "image_box" not in batch
    img = np.asarray(batch["image"])
    for i, rid in enumerate(np.asarray(batch["id"])):
        h, w = sizes[rid]
        _assert_close(img[i], _reference(blobs[rid], (0, 0, h, w, 0), (32, 32)), rid)


def test_overflow_ships_blocks_past_the_capacity(small_canvases, monkeypatch, counted):
    """A batch that outgrows its flat arrays' capacity by less than a half ships
    the rest in the overflow arrays: the programs a batch that fits compiled
    serve it, and no capacity grows."""
    from jax import monitoring

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_k: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    fits = [(40, 50), (60, 33), (100, 70), (17, 23), (100, 129)]
    over = [(40, 50), (60, 33), (100, 70), (17, 23), (130, 200)]  # more blocks
    # the larger image's coarser quantization keeps its coefficients inside the
    # first batch's narrowing tiers, so only the block count differs
    first = _blobs(fits, seed=21)
    batches = [(fits, first), (over, first[:4] + _blobs(over[4:], seed=21, quality=70))]
    planes = [_planes(blobs) for _s, blobs in batches]
    totals = [sum(math.prod(p.components[c].blocks.shape[:2]) for p in planes[1])
              for c in range(3)]
    caps = {(("canvas", 3), c): int(t / 1.4) for c, t in enumerate(totals)}
    monkeypatch.setattr(J, "_CAPS", dict(caps))
    policy = C.parse_policy({"size": [24, 24], "crop": "random_resized", "flip": 0.5})
    for i, ((sizes, blobs), ps) in enumerate(zip(batches, planes)):
        boxes = C.draw_boxes(sizes, policy, C.generator(3, i))
        before = len(compiles)
        out = np.asarray(J.decode_jpeg_crop_batch(ps, boxes, (24, 24)))
        assert J._CAPS == caps  # in capacity, then in capacity and overflow
        if i:
            assert len(compiles) == before, compiles[before:]
        for j in range(len(sizes)):
            _assert_close(out[j], _reference(blobs[j], boxes[j], (24, 24)), sizes[j])


def test_crop_decode_under_a_sharded_batch_counts_and_warns(tmp_path, caplog):
    """The canvas decode runs on one device: under an 8-way batch sharding every
    batch counts in ``decode_unsharded_batches`` and the loader warns once, and
    the delivered batch still lands on the sharding."""
    import logging

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from petastorm_tpu.loader import DataLoader
    from petastorm_tpu.reader import make_batch_reader

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    sizes = [(40, 60), (70, 30), (50, 50), (64, 64), (33, 47), (60, 60), (20, 64),
             (48, 48)] * 2
    url, blobs = _store(tmp_path, sizes)
    reader = make_batch_reader(url, decode_on_device=True, num_epochs=1,
                               shuffle_row_groups=False)
    with caplog.at_level(logging.WARNING, logger="petastorm_tpu.obs"):
        with DataLoader(reader, batch_size=8, sharding=sharding,
                        device_decode_resize={"image": POLICY}) as loader:
            batches = list(loader)
            stats = loader.stats
    assert len(batches) == 2 and stats.decode_unsharded_batches == 2
    for batch in batches:
        assert batch["image"].sharding.is_equivalent_to(sharding, 4)
        img, box = np.asarray(batch["image"]), np.asarray(batch["image_box"])
        for i, rid in enumerate(np.asarray(batch["id"])):
            _assert_close(img[i], _reference(blobs[rid], box[i], (48, 40)), rid)
    warnings = [r.getMessage() for r in caplog.records
                if "SINGLE device" in r.getMessage()]
    assert len(warnings) == 1 and "canvas decode" in warnings[0]
