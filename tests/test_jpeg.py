"""Two-stage JPEG decode vs the cv2 (libjpeg) oracle: entropy decode + Pallas IDCT."""
import dataclasses

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from petastorm_tpu.ops.jpeg import (  # noqa: E402
    decode_jpeg,
    decode_jpeg_device_stage,
    entropy_decode_jpeg,
    idct_blocks,
)


def _roundtrip(img, quality=90):
    ok, enc = cv2.imencode(".jpg", cv2.cvtColor(img, cv2.COLOR_RGB2BGR),
                           [cv2.IMWRITE_JPEG_QUALITY, quality])
    assert ok
    data = enc.tobytes()
    ref = cv2.cvtColor(cv2.imdecode(np.frombuffer(data, np.uint8), cv2.IMREAD_COLOR),
                       cv2.COLOR_BGR2RGB)
    return data, ref


def _raw_cell_images(qualities, size, seed=5, per_quality=1):
    """Images of the raw ImageNet cell's model (``bench/writers/jpeg_raw_store``: a
    blurred noise field over a gradient, a hard-edged patch and fine texture, 4:2:0)
    with both sides drawn from ``size``; one JPEG a quality and index."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench", "writers",
                        "jpeg_raw_store.py")
    spec = importlib.util.spec_from_file_location("jpeg_raw_store", path)
    writer = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(writer)
    return [writer._image({"size_mixture": [{"share": 1.0, "both": list(size)}],
                           "jpeg_quality": q}, seed, i)
            for q in qualities for i in range(per_quality)]


def _extreme_gray_q100():
    """Grayscale q100: 8x8 blocks alternating 0/255 (DC differences of category 11)
    over 1-pixel stripes (AC values of size 10)."""
    img = np.zeros((64, 64), np.uint8)
    by, bx = np.indices((4, 8))
    img[:32] = np.kron((by + bx) % 2 * 255, np.ones((8, 8))).astype(np.uint8)
    img[32:, ::2] = 255
    ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 100])
    assert ok
    return enc.tobytes()


def _assert_profile_is_the_separate_passes(planes):
    """A stage-1 result's folded ``specmax`` and ``kmax`` equal what
    ``jpeg_specmax_native`` and ``flatnonzero`` give over its decoded blocks."""
    from petastorm_tpu.ops import native

    for c, comp in enumerate(planes.components):
        want = native.jpeg_specmax_native(
            np.ascontiguousarray(comp.blocks, dtype=np.int16).reshape(1, -1, 64))
        np.testing.assert_array_equal(planes.specmax[c], want)
        assert planes.kmax[c] == (np.flatnonzero(want).max() if want.any() else 0)


def test_gradient_image_close_to_libjpeg():
    gx = np.tile(np.linspace(0, 255, 64)[None, :], (48, 1))
    gy = np.tile(np.linspace(0, 255, 48)[:, None], (1, 64))
    img = np.stack([gx, gy, 0.5 * (gx + gy)], -1).astype(np.uint8)
    data, ref = _roundtrip(img, 90)
    ours = np.asarray(decode_jpeg(data))
    diff = np.abs(ref.astype(int) - ours.astype(int))
    assert diff.max() <= 4 and diff.mean() < 1.0


def test_noise_image_within_lossy_tolerance():
    rng = np.random.RandomState(0)
    img = rng.randint(0, 256, (40, 56, 3), dtype=np.uint8)
    data, ref = _roundtrip(img, 75)
    ours = np.asarray(decode_jpeg(data))
    diff = np.abs(ref.astype(int) - ours.astype(int))
    assert diff.mean() < 2.0  # float IDCT vs libjpeg fixed-point islow
    assert np.percentile(diff, 99) <= 10


def test_odd_size_and_flat():
    img = (np.ones((17, 19, 3)) * [10, 200, 60]).astype(np.uint8)
    data, ref = _roundtrip(img, 80)
    ours = np.asarray(decode_jpeg(data))
    assert ours.shape == (17, 19, 3)
    assert np.abs(ref.astype(int) - ours.astype(int)).max() <= 1


def test_grayscale_near_exact():
    rng = np.random.RandomState(1)
    gray = rng.randint(0, 256, (40, 56), dtype=np.uint8)
    ok, enc = cv2.imencode(".jpg", gray, [cv2.IMWRITE_JPEG_QUALITY, 95])
    data = enc.tobytes()
    ref = cv2.imdecode(np.frombuffer(data, np.uint8), cv2.IMREAD_GRAYSCALE)
    ours = np.asarray(decode_jpeg(data))
    assert ours.shape == ref.shape + (3,)
    assert np.abs(ref.astype(int) - ours[:, :, 0].astype(int)).max() <= 1


def test_stage1_block_structure():
    rng = np.random.RandomState(2)
    img = rng.randint(0, 256, (32, 48, 3), dtype=np.uint8)
    data, _ = _roundtrip(img, 90)
    planes = entropy_decode_jpeg(data)
    assert planes.height == 32 and planes.width == 48
    y = planes.components[0]
    assert y.blocks.shape[2] == 64
    assert y.blocks.shape[0] * 8 >= 32 and y.blocks.shape[1] * 8 >= 48
    assert y.qtable.shape == (64,)


def test_idct_blocks_matches_scipy_style_reference():
    rng = np.random.RandomState(3)
    coeffs = rng.randint(-64, 64, (10, 64)).astype(np.int32)
    q = np.ones(64, np.int32)
    out = np.asarray(idct_blocks(coeffs, q))
    # dense float reference
    a = np.zeros((8, 8))
    for u in range(8):
        alpha = np.sqrt(0.25) if u else np.sqrt(0.125)
        for p in range(8):
            a[u, p] = alpha * np.cos((2 * p + 1) * u * np.pi / 16.0)
    basis = np.kron(a, a)
    expected = coeffs.astype(np.float64) @ basis + 128.0
    np.testing.assert_allclose(out, expected, atol=1e-3)


def test_native_stage1_bit_exact_vs_python_oracle():
    """C++ entropy decoder must produce identical coefficients/qtables to the Python
    reference, across samplings, restart intervals, grayscale and odd sizes."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import entropy_decode_jpeg_fast

    rng = np.random.RandomState(7)
    streams = []
    for shape, opts in [
        ((64, 48, 3), [cv2.IMWRITE_JPEG_QUALITY, 90]),
        ((128, 128, 3), [cv2.IMWRITE_JPEG_QUALITY, 85, cv2.IMWRITE_JPEG_RST_INTERVAL, 2]),
        ((17, 19, 3), [cv2.IMWRITE_JPEG_QUALITY, 80]),
        ((224, 224, 3), [cv2.IMWRITE_JPEG_QUALITY, 95, cv2.IMWRITE_JPEG_OPTIMIZE, 1]),
    ]:
        ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, shape, dtype=np.uint8), opts)
        assert ok
        streams.append(enc.tobytes())
    ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (40, 56), dtype=np.uint8),
                           [cv2.IMWRITE_JPEG_QUALITY, 95])
    streams.append(enc.tobytes())
    # the raw cell's noisy originals at q90 and q95 (at its smallest sizes: the
    # oracle reads bit by bit), one with restart intervals; 4:4:4 and 4:2:2; the
    # largest DC categories and AC sizes baseline allows
    streams += _raw_cell_images((90, 95), (150, 164))
    img = cv2.imdecode(np.frombuffer(streams[-1], np.uint8), cv2.IMREAD_COLOR)
    for opts in ([cv2.IMWRITE_JPEG_QUALITY, 90, cv2.IMWRITE_JPEG_RST_INTERVAL, 3],
                 [cv2.IMWRITE_JPEG_QUALITY, 92, cv2.IMWRITE_JPEG_SAMPLING_FACTOR,
                  cv2.IMWRITE_JPEG_SAMPLING_FACTOR_444],
                 [cv2.IMWRITE_JPEG_QUALITY, 92, cv2.IMWRITE_JPEG_SAMPLING_FACTOR,
                  cv2.IMWRITE_JPEG_SAMPLING_FACTOR_422]):
        ok, enc = cv2.imencode(".jpg", img[:64, :80], opts)
        assert ok
        streams.append(enc.tobytes())
    streams.append(_extreme_gray_q100())

    for data in streams:
        py = entropy_decode_jpeg(data)
        nat = entropy_decode_jpeg_fast(data)
        _assert_profile_is_the_separate_passes(nat)
        assert (py.height, py.width) == (nat.height, nat.width)
        assert len(py.components) == len(nat.components)
        for pc, nc in zip(py.components, nat.components):
            assert (pc.h_samp, pc.v_samp) == (nc.h_samp, nc.v_samp)
            np.testing.assert_array_equal(pc.blocks, nc.blocks.astype(np.int32))
            np.testing.assert_array_equal(pc.qtable, nc.qtable)
    # the cases reach what the one-lookup path must hand on or decode whole: 0xFF00
    # stuffing, DC differences of category 11 (grayscale: one block an MCU, in
    # raster order) and AC values of size 10
    assert all(b"\xff\x00" in data for data in streams[5:7])
    blocks = entropy_decode_jpeg_fast(streams[-1]).components[0].blocks.reshape(-1, 64)
    assert np.abs(np.diff(blocks[:, 0].astype(np.int32), prepend=0)).max() >= 1024
    assert np.abs(blocks[:, 1:]).max() >= 512


def test_native_stage1_rejects_bad_streams():
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    with pytest.raises(ValueError, match="SOI"):
        native.jpeg_decode_image_native(b"\x00\x01\x02\x03")
    # lossless (SOF3) stays unsupported
    lossless = (b"\xff\xd8\xff\xc3\x00\x0b\x08\x00\x10\x00\x10\x01\x01\x11\x00"
                b"\xff\xd9")
    with pytest.raises(ValueError, match="[Uu]nsupported"):
        native.jpeg_decode_image_native(lossless)


def test_native_progressive_matches_cv2():
    """Progressive JPEG (SOF2: spectral selection + successive approximation) decodes
    natively through the two-stage path within lossy tolerance of cv2 — including
    optimized Huffman tables, restart intervals, odd sizes, grayscale."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import (decode_jpeg_device_stage,
                                        entropy_decode_jpeg_fast)

    rng = np.random.RandomState(31)
    cases = [
        ((40, 56, 3), [cv2.IMWRITE_JPEG_QUALITY, 75, cv2.IMWRITE_JPEG_PROGRESSIVE, 1]),
        ((17, 19, 3), [cv2.IMWRITE_JPEG_QUALITY, 85, cv2.IMWRITE_JPEG_PROGRESSIVE, 1,
                       cv2.IMWRITE_JPEG_OPTIMIZE, 1]),
        ((64, 64, 3), [cv2.IMWRITE_JPEG_QUALITY, 90, cv2.IMWRITE_JPEG_PROGRESSIVE, 1,
                       cv2.IMWRITE_JPEG_RST_INTERVAL, 2]),
        ((48, 48), [cv2.IMWRITE_JPEG_QUALITY, 90, cv2.IMWRITE_JPEG_PROGRESSIVE, 1]),
    ]
    cases = [(rng.randint(0, 256, shape, dtype=np.uint8), opts) for shape, opts in cases]
    # the raw cell's noisy originals, re-encoded progressive at q95 (4:2:0)
    raw = cv2.imdecode(np.frombuffer(_raw_cell_images((95,), (150, 164))[0], np.uint8),
                       cv2.IMREAD_COLOR)
    cases += [(raw, [cv2.IMWRITE_JPEG_QUALITY, 95, cv2.IMWRITE_JPEG_PROGRESSIVE, 1]),
              (raw, [cv2.IMWRITE_JPEG_QUALITY, 95, cv2.IMWRITE_JPEG_PROGRESSIVE, 1,
                     cv2.IMWRITE_JPEG_OPTIMIZE, 1, cv2.IMWRITE_JPEG_RST_INTERVAL, 5])]
    for img, opts in cases:
        shape = img.shape
        ok, enc = cv2.imencode(".jpg", img, opts)
        assert ok
        data = enc.tobytes()
        flag = cv2.IMREAD_GRAYSCALE if len(shape) == 2 else cv2.IMREAD_COLOR
        ref = cv2.imdecode(np.frombuffer(data, np.uint8), flag)
        if ref.ndim == 2:
            ref = np.stack([ref] * 3, -1)
        planes = entropy_decode_jpeg_fast(data)
        _assert_profile_is_the_separate_passes(planes)
        ours = np.asarray(decode_jpeg_device_stage(planes))
        # our stage-2 output is RGB; cv2 color reads BGR
        if len(shape) == 3:
            ours = ours[:, :, ::-1]
        diff = np.abs(ref.astype(int) - ours.astype(int))
        assert diff.mean() < 2.0, (shape, opts, diff.mean())
        assert np.percentile(diff, 99) <= 10, (shape, opts)


def test_batched_stage1_mixed_baseline_and_progressive():
    """Same-layout baseline and progressive streams decode together in one batch call
    (the batch verifies layout, not coding mode)."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import (entropy_decode_jpeg_batch,
                                        entropy_decode_jpeg_fast)

    rng = np.random.RandomState(32)
    img = rng.randint(0, 256, (32, 48, 3), dtype=np.uint8)
    ok, enc_b = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90])
    ok, enc_p = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90,
                                           cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
    blobs = [enc_b.tobytes(), enc_p.tobytes(), enc_b.tobytes()]
    batch = entropy_decode_jpeg_batch(blobs)
    assert all(p is not None for p in batch)
    for p, blob in zip(batch, blobs):
        ref = entropy_decode_jpeg_fast(blob)
        for pc, rc in zip(p.components, ref.components):
            np.testing.assert_array_equal(pc.blocks, rc.blocks)


def test_native_stage1_throughput_beats_python():
    """The native decoder is the 'fast enough to matter' requirement: it must beat the
    pure-Python oracle by orders of magnitude (sanity floor: 50x on one image)."""
    import time

    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(11)
    img = rng.randint(0, 256, (128, 128, 3), dtype=np.uint8)
    ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 85])
    data = enc.tobytes()
    native.jpeg_decode_image_native(data)  # warm (build cached)
    t_native = float("inf")
    for _ in range(5):  # min-of-N: one scheduler hiccup must not fail the suite
        t0 = time.perf_counter()
        native.jpeg_decode_image_native(data)
        t_native = min(t_native, time.perf_counter() - t0)
    t0 = time.perf_counter()
    entropy_decode_jpeg(data)
    t_py = time.perf_counter() - t0
    assert t_py / t_native > 50


def test_batched_stage2_matches_per_image():
    """decode_jpeg_batch: one batched dispatch must equal N per-image decodes, with
    per-image quantization tables (mixed qualities in one batch)."""
    from petastorm_tpu.ops.jpeg import decode_jpeg_batch, entropy_decode_jpeg_fast

    rng = np.random.RandomState(5)
    planes = []
    refs = []
    for quality in (75, 90, 95):
        img = rng.randint(0, 256, (48, 64, 3), dtype=np.uint8)
        ok, enc = cv2.imencode(".jpg", cv2.cvtColor(img, cv2.COLOR_RGB2BGR),
                               [cv2.IMWRITE_JPEG_QUALITY, quality])
        assert ok
        p = entropy_decode_jpeg_fast(enc.tobytes())
        planes.append(p)
        refs.append(np.asarray(decode_jpeg_device_stage(p)))
    batch = np.asarray(decode_jpeg_batch(planes))
    assert batch.shape == (3, 48, 64, 3)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(batch[i], ref)


def test_batched_stage2_mixed_sampling_groups():
    """Gray (1 component) and color (3 components, 4:2:0) in one batch: grouped decode
    must restore input order."""
    from petastorm_tpu.ops.jpeg import decode_jpeg_batch, entropy_decode_jpeg_fast

    rng = np.random.RandomState(6)
    color = rng.randint(0, 256, (32, 48, 3), dtype=np.uint8)
    gray = rng.randint(0, 256, (32, 48), dtype=np.uint8)
    ok1, enc_c = cv2.imencode(".jpg", cv2.cvtColor(color, cv2.COLOR_RGB2BGR),
                              [cv2.IMWRITE_JPEG_QUALITY, 90])
    ok2, enc_g = cv2.imencode(".jpg", gray, [cv2.IMWRITE_JPEG_QUALITY, 90])
    assert ok1 and ok2
    p_color = entropy_decode_jpeg_fast(enc_c.tobytes())
    p_gray = entropy_decode_jpeg_fast(enc_g.tobytes())
    batch = np.asarray(decode_jpeg_batch([p_color, p_gray, p_color]))
    assert batch.shape == (3, 32, 48, 3)
    np.testing.assert_array_equal(batch[0], np.asarray(decode_jpeg_device_stage(p_color)))
    np.testing.assert_array_equal(batch[1], np.asarray(decode_jpeg_device_stage(p_gray)))
    np.testing.assert_array_equal(batch[2], batch[0])


def test_batched_stage2_rejects_mixed_sizes():
    from petastorm_tpu.ops.jpeg import decode_jpeg_batch, entropy_decode_jpeg_fast

    rng = np.random.RandomState(8)
    out = []
    for shape in ((32, 32, 3), (48, 32, 3)):
        ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, shape, dtype=np.uint8),
                               [cv2.IMWRITE_JPEG_QUALITY, 90])
        out.append(entropy_decode_jpeg_fast(enc.tobytes()))
    with pytest.raises(ValueError, match="uniform image size"):
        decode_jpeg_batch(out)


def test_progressive_rejected_by_python_oracle_only():
    """The pure-Python ORACLE stays baseline-only (it exists for bit-exact baseline
    verification); progressive streams are the native decoder's job — covered by
    test_native_progressive_matches_cv2."""
    rng = np.random.RandomState(4)
    img = rng.randint(0, 256, (32, 32, 3), dtype=np.uint8)
    ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90,
                                         cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
    with pytest.raises(ValueError, match="progressive|Unsupported"):
        entropy_decode_jpeg(enc.tobytes())


def test_not_a_jpeg_rejected():
    with pytest.raises(ValueError, match="SOI"):
        entropy_decode_jpeg(b"\x00\x01\x02")


def test_rowgroup_batched_stage1_matches_per_image():
    """entropy_decode_jpeg_batch: one native call over a row group must produce planes
    identical to the per-image path, with zero-copy views carrying a batch_ref."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import (entropy_decode_jpeg_batch,
                                        entropy_decode_jpeg_fast)

    rng = np.random.RandomState(21)
    blobs = []
    for quality in (75, 85, 95, 90):
        ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (48, 64, 3), dtype=np.uint8),
                               [cv2.IMWRITE_JPEG_QUALITY, quality])
        assert ok
        blobs.append(enc.tobytes())
    # and a row group of the raw cell's noisy originals at one of its sizes, q90/q95
    for group in (blobs, _raw_cell_images((90, 95), (375, 375), per_quality=2)):
        batch = entropy_decode_jpeg_batch(group)
        assert all(p is not None for p in batch)
        for p, blob in zip(batch, group):
            ref = entropy_decode_jpeg_fast(blob)
            _assert_profile_is_the_separate_passes(ref)
            assert (p.height, p.width) == (ref.height, ref.width)
            assert p.batch_ref is not None
            for pc, rc in zip(p.components, ref.components):
                assert (pc.h_samp, pc.v_samp) == (rc.h_samp, rc.v_samp)
                np.testing.assert_array_equal(pc.blocks, rc.blocks)
                np.testing.assert_array_equal(pc.qtable, rc.qtable)
                # views into the stacked parent, not copies
                assert pc.blocks.base is not None
        # the row group's folded profile: the separate pass over its stacked buffers,
        # and the elementwise max of its images' own
        coeffs = batch[0].batch_ref[0]
        for c, stacked in enumerate(coeffs):
            want = native.jpeg_specmax_native(stacked)
            np.testing.assert_array_equal(batch[0].specmax[c], want)
            assert batch[0].kmax[c] == (np.flatnonzero(want).max() if want.any() else 0)
        np.testing.assert_array_equal(
            batch[0].specmax,
            np.maximum.reduce([entropy_decode_jpeg_fast(b).specmax for b in group]))


def test_rowgroup_batched_stage1_bad_rows_are_none():
    """A corrupt stream or a layout-mismatched stream mid-batch yields None at that
    position; every other stream still decodes."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import entropy_decode_jpeg_batch

    rng = np.random.RandomState(22)
    ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (32, 48, 3), dtype=np.uint8),
                           [cv2.IMWRITE_JPEG_QUALITY, 90])
    good = enc.tobytes()
    ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (64, 48, 3), dtype=np.uint8),
                           [cv2.IMWRITE_JPEG_QUALITY, 90])
    other_layout = enc.tobytes()
    batch = entropy_decode_jpeg_batch([good, good[:40], other_layout, good])
    assert batch[0] is not None and batch[3] is not None
    assert batch[1] is None  # truncated
    assert batch[2] is None  # layout differs from the batch layout
    np.testing.assert_array_equal(batch[0].components[0].blocks,
                                  batch[3].components[0].blocks)


def test_stack_jpeg_coefficients_view_fast_path():
    """Batch-ref rows must stack via parent slicing/gather, equal to np.stack of the
    per-row objects, for consecutive, shuffled, and mixed-parent inputs."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import (entropy_decode_jpeg_batch,
                                        entropy_decode_jpeg_fast,
                                        stack_jpeg_coefficients)

    rng = np.random.RandomState(23)
    blobs = []
    for _ in range(6):
        ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (32, 32, 3), dtype=np.uint8),
                               [cv2.IMWRITE_JPEG_QUALITY, 88])
        blobs.append(enc.tobytes())
    batch = entropy_decode_jpeg_batch(blobs)
    ref_rows = [entropy_decode_jpeg_fast(b) for b in blobs]

    for pick in ([0, 1, 2, 3], [4, 1, 5, 0]):  # consecutive slice; shuffled gather
        got_c, got_q = stack_jpeg_coefficients([batch[i] for i in pick])
        exp_c, exp_q = stack_jpeg_coefficients([ref_rows[i] for i in pick])
        for g, e in zip(got_c, exp_c):
            np.testing.assert_array_equal(g, e)
        for g, e in zip(got_q, exp_q):
            np.testing.assert_array_equal(g, e)
    # mixed parents (rows from two row groups) falls back to np.stack and still matches
    batch2 = entropy_decode_jpeg_batch(blobs[:3])
    got_c, got_q = stack_jpeg_coefficients([batch[5], batch2[1]])
    exp_c, exp_q = stack_jpeg_coefficients([ref_rows[5], ref_rows[1]])
    for g, e in zip(got_c, exp_c):
        np.testing.assert_array_equal(g, e)


def test_codec_host_stage_decode_batch_contract():
    """CompressedImageCodec.host_stage_decode_batch: Nones preserved, undecodable rows
    come back as host-decoded ndarrays, good rows as JpegPlanes."""
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.ops.jpeg import JpegPlanes
    from petastorm_tpu.unischema import UnischemaField

    rng = np.random.RandomState(24)
    codec = CompressedImageCodec("jpeg", 90)
    field = UnischemaField("image", np.uint8, (32, 48, 3), codec, False)
    img = rng.randint(0, 256, (32, 48, 3), dtype=np.uint8)
    blob = bytes(codec.encode(field, img))
    ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (32, 48, 3), dtype=np.uint8),
                           [cv2.IMWRITE_JPEG_QUALITY, 90, cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
    progressive = enc.tobytes()

    out = codec.host_stage_decode_batch(field, [blob, None, progressive, blob])
    assert isinstance(out[0], (JpegPlanes, np.ndarray))
    assert out[1] is None
    # progressive: native decodes it to planes; pure-Python fallback path (native
    # unavailable) host-decodes it to an ndarray — both honor the contract
    from petastorm_tpu.ops import native
    if native.native_available():
        assert isinstance(out[2], JpegPlanes)
    else:
        assert isinstance(out[2], np.ndarray) and out[2].shape == (32, 48, 3)


def test_second_sof_rejected_not_crash():
    """A stream with a second frame header after a decoded scan must raise a clean
    ValueError — re-parsing frame geometry while coefficient buffers keep the first
    frame's layout was a segfault (null/oob write through stale block pointers)."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(33)
    img = rng.randint(0, 256, (16, 16, 3), dtype=np.uint8)
    ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90,
                                         cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
    data = enc.tobytes()
    # locate this stream's own SOF2 segment to replay it before EOI
    sof = data.find(b"\xff\xc2")
    seglen = (data[sof + 2] << 8) | data[sof + 3]
    sof_seg = data[sof:sof + 2 + seglen]
    big_sof = bytearray(sof_seg)
    big_sof[5:7] = (1024).to_bytes(2, "big")   # second frame claims 1024x1024
    big_sof[7:9] = (1024).to_bytes(2, "big")
    assert data.endswith(b"\xff\xd9")
    evil = data[:-2] + bytes(big_sof) + data[sof:len(data)]  # 2nd SOF + scans + EOI
    with pytest.raises(ValueError):
        native.jpeg_decode_image_native(evil)


def test_progressive_coefficients_bit_exact_vs_baseline():
    """Encoding the same pixels at the same quality baseline vs progressive transmits
    the SAME quantized coefficients (progressive only reorders them) — so native
    progressive decode must be bit-exact against native baseline decode."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import entropy_decode_jpeg_fast

    rng = np.random.RandomState(41)
    raw = cv2.imdecode(np.frombuffer(_raw_cell_images((90,), (150, 164))[0], np.uint8),
                       cv2.IMREAD_COLOR)
    for img in [rng.randint(0, 256, shape, dtype=np.uint8)
                for shape in ((64, 80, 3), (17, 19, 3), (48, 48))] + [raw]:
        ok, b = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 88])
        ok, p = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 88,
                                           cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
        base = entropy_decode_jpeg_fast(b.tobytes())
        prog = entropy_decode_jpeg_fast(p.tobytes())
        assert len(base.components) == len(prog.components)
        for bc, pc in zip(base.components, prog.components):
            np.testing.assert_array_equal(bc.blocks, pc.blocks)
            np.testing.assert_array_equal(bc.qtable, pc.qtable)


def test_truncated_streams_never_crash():
    """Every truncation of baseline and progressive streams must either decode or
    raise ValueError — never crash the worker process."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(42)
    img = rng.randint(0, 256, (32, 32, 3), dtype=np.uint8)
    streams = []
    for opts in ([cv2.IMWRITE_JPEG_QUALITY, 90],
                 [cv2.IMWRITE_JPEG_QUALITY, 90, cv2.IMWRITE_JPEG_PROGRESSIVE, 1]):
        ok, enc = cv2.imencode(".jpg", img, opts)
        streams.append((enc.tobytes(), 23))
    # a raw-cell original: cuts inside long runs of one-lookup codes
    streams.append((_raw_cell_images((95,), (150, 164))[0], 331))
    for data, step in streams:
        for cut in range(2, len(data), step):
            try:
                native.jpeg_decode_image_native(data[:cut])
            except (ValueError, RuntimeError):
                pass
            try:
                native.jpeg_decode_coeffs_batch_native([data, data[:cut]])
            except (ValueError, RuntimeError):
                pass


def test_kmax_bound_and_truncated_decode_bit_equal():
    """The batch decoder's kmax must bound every nonzero zigzag index, the native
    zigzag pack must equal the numpy gather, and the truncated device decode must be
    BIT-equal to the per-image path (truncation only drops guaranteed zeros)."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import (ZIGZAG, decode_jpeg_batch,
                                        entropy_decode_jpeg_batch,
                                        entropy_decode_jpeg_fast,
                                        stack_jpeg_coefficients, _truncation_ks)

    rng = np.random.RandomState(61)
    # smooth images -> sparse spectra -> truncation path taken
    blobs = []
    for _ in range(6):
        img = cv2.GaussianBlur(rng.randint(0, 256, (48, 64, 3)).astype(np.float32),
                               (7, 7), 2.0).clip(0, 255).astype(np.uint8)
        ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 85])
        blobs.append(enc.tobytes())
    batch = entropy_decode_jpeg_batch(blobs)
    assert batch[0].kmax is not None
    coeffs, _ = stack_jpeg_coefficients(batch)
    for c, arr in enumerate(coeffs):
        nz = np.where((arr != 0).any(axis=(0, 1))[ZIGZAG])[0]
        true_kmax = int(nz[-1]) if len(nz) else 0
        assert batch[0].kmax[c] >= true_kmax
        # folded into the decode, it is the highest nonzero position itself, and
        # the profile is the separate pass's
        assert batch[0].kmax[c] == true_kmax
        np.testing.assert_array_equal(batch[0].specmax[c],
                                      native.jpeg_specmax_native(arr))
    for blob in blobs:
        _assert_profile_is_the_separate_passes(entropy_decode_jpeg_fast(blob))

    ks = _truncation_ks(batch)
    assert ks is not None  # smooth data must actually exercise the packed path
    packed = native.jpeg_zigzag_truncate_native(coeffs[0], ks[0])
    np.testing.assert_array_equal(packed, coeffs[0][:, :, ZIGZAG[:ks[0]]])

    out = np.asarray(decode_jpeg_batch(batch))
    for i, blob in enumerate(blobs):
        ref = np.asarray(decode_jpeg_device_stage(entropy_decode_jpeg_fast(blob)))
        np.testing.assert_array_equal(out[i], ref)


def test_kmax_survives_detach_and_pickle():
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    import pickle

    from petastorm_tpu.ops.jpeg import entropy_decode_jpeg_batch

    rng = np.random.RandomState(62)
    ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (32, 32, 3), dtype=np.uint8),
                           [cv2.IMWRITE_JPEG_QUALITY, 90])
    p = entropy_decode_jpeg_batch([enc.tobytes()])[0]
    assert p.kmax is not None
    assert p.detach().kmax == p.kmax
    assert pickle.loads(pickle.dumps(p)).kmax == p.kmax


def test_per_image_stage1_arrays_own_their_memory():
    """The per-image stage 1 decodes into arrays of its own: no native buffer is
    freed under them when the call returns, and they survive ``detach()``, pickling
    and the next decodes of the thread unchanged."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    import gc
    import pickle

    from petastorm_tpu.ops.jpeg import entropy_decode_jpeg_fast

    blobs = _raw_cell_images((90, 95), (150, 164))
    p = entropy_decode_jpeg_fast(blobs[0])
    want = [(c.blocks.copy(), c.qtable.copy()) for c in p.components]
    for c in p.components:
        assert c.blocks.flags.owndata and c.blocks.dtype == np.int16
        assert isinstance(c.qtable.base, np.ndarray) and c.qtable.base.flags.owndata
    for blob in blobs * 3:  # the thread's next decodes reuse nothing of p's
        entropy_decode_jpeg_fast(blob)
    gc.collect()
    for got in (p, p.detach(), pickle.loads(pickle.dumps(p))):
        assert got.kmax == p.kmax
        np.testing.assert_array_equal(got.specmax, p.specmax)
        for c, (blocks, qtable) in zip(got.components, want):
            np.testing.assert_array_equal(c.blocks, blocks)
            np.testing.assert_array_equal(c.qtable, qtable)


def test_truncated_decode_with_progressive_streams():
    """kmax tracking covers progressive scans too: smooth progressive JPEGs take the
    zigzag-prefix path and the output stays bit-equal to the per-image decode."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    from petastorm_tpu.ops.jpeg import (decode_jpeg_batch, entropy_decode_jpeg_batch,
                                        entropy_decode_jpeg_fast, _truncation_ks)

    rng = np.random.RandomState(63)
    blobs = []
    for _ in range(4):
        img = cv2.GaussianBlur(rng.randint(0, 256, (48, 48, 3)).astype(np.float32),
                               (9, 9), 3.0).clip(0, 255).astype(np.uint8)
        ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 80,
                                             cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
        blobs.append(enc.tobytes())
    batch = entropy_decode_jpeg_batch(blobs)
    assert all(p is not None and p.kmax is not None for p in batch)
    assert _truncation_ks(batch) is not None
    out = np.asarray(decode_jpeg_batch(batch))
    for i, blob in enumerate(blobs):
        ref = np.asarray(decode_jpeg_device_stage(entropy_decode_jpeg_fast(blob)))
        np.testing.assert_array_equal(out[i], ref)


def test_pack12_roundtrip_and_overflow():
    """12-bit coefficient pack: exact byte layout vs a numpy reference, range check
    returns None on overflow, odd trailing dim rejected."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(77)
    src = rng.randint(-2048, 2048, (3, 5, 16)).astype(np.int16)
    packed = native.jpeg_pack12_native(src)
    assert packed is not None and packed.shape == (3, 5, 24)
    flat = src.reshape(-1)
    out = packed.reshape(-1)
    for i in range(0, len(flat), 2):
        a, b = int(flat[i]) & 0xFFF, int(flat[i + 1]) & 0xFFF
        j = (i // 2) * 3
        assert out[j] == (a & 0xFF)
        assert out[j + 1] == (((a >> 8) & 0xF) | ((b & 0xF) << 4))
        assert out[j + 2] == ((b >> 4) & 0xFF)
    # overflow anywhere -> None (caller ships int16)
    src2 = src.copy()
    src2[1, 2, 3] = 2048
    assert native.jpeg_pack12_native(src2) is None
    src2[1, 2, 3] = -2049
    assert native.jpeg_pack12_native(src2) is None
    with pytest.raises(ValueError, match="even"):
        native.jpeg_pack12_native(src[:, :, :15])


def test_specmax_native_matches_numpy():
    """Per-zigzag-position max |coeff|: natural-order and zigzag-prefix modes vs a
    numpy reference."""
    from petastorm_tpu.ops import native
    from petastorm_tpu.ops.jpeg import ZIGZAG

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(31)
    src = rng.randint(-900, 900, (4, 7, 64)).astype(np.int16)
    got = native.jpeg_specmax_native(src)
    flat = src.reshape(-1, 64)
    exp = np.abs(flat[:, np.asarray(ZIGZAG)]).max(axis=0)
    np.testing.assert_array_equal(got, exp)
    # zigzag-prefix mode: rows already in zigzag order, any width
    srcz = rng.randint(-50, 50, (3, 5, 16)).astype(np.int16)
    gotz = native.jpeg_specmax_native(srcz, is_zigzag=True)
    np.testing.assert_array_equal(gotz, np.abs(srcz.reshape(-1, 16)).max(axis=0))
    with pytest.raises(ValueError, match="64"):
        native.jpeg_specmax_native(srcz)  # natural mode requires width 64


def test_pack_split_native_roundtrip():
    """Spectral-split pack: slab layout vs a numpy reference unpack across edge
    splits; per-tier range failures return None; validation errors raise."""
    from petastorm_tpu.ops import native
    from petastorm_tpu.ops.jpeg import ZIGZAG

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(32)

    def unpack(head, mid, tail, k1, k2, k):
        n, nb = mid.shape[:2]
        out = np.empty((n, nb, k), dtype=np.int16)
        h = head.reshape(n, nb, -1, 3).astype(np.int32)
        lo = h[..., 0] | ((h[..., 1] & 0xF) << 8)
        hi = (h[..., 1] >> 4) | (h[..., 2].astype(np.int32) << 4)
        pair = np.stack([lo, hi], axis=-1)
        pair = pair - ((pair & 0x800) << 1)
        out[..., :k1] = pair.reshape(n, nb, -1)
        out[..., k1:k2] = mid
        t = tail.astype(np.int32)
        tl, th = t & 0xF, (t >> 4) & 0xF
        tp = np.stack([tl, th], axis=-1)
        tp = tp - ((tp & 0x8) << 1)
        out[..., k2:] = tp.reshape(n, nb, -1)
        return out

    # zigzag-order input with a realistic spectral profile
    for (k1, k2, k) in [(8, 52, 64), (0, 12, 64), (4, 4, 16), (0, 0, 16),
                        (16, 16, 16), (0, 64, 64), (64, 64, 64)]:
        src = np.zeros((3, 6, k), dtype=np.int16)
        if k1:
            src[..., :k1] = rng.randint(-2048, 2048, (3, 6, k1))
        if k2 > k1:
            src[..., k1:k2] = rng.randint(-128, 128, (3, 6, k2 - k1))
        if k > k2:
            src[..., k2:] = rng.randint(-8, 8, (3, 6, k - k2))
        res = native.jpeg_pack_split_native(src, k1, k2, is_zigzag=True)
        assert res is not None, (k1, k2, k)
        head, mid, tail = res
        assert head.shape == (3, 6, k1 * 3 // 2)
        assert mid.shape == (3, 6, k2 - k1)
        assert tail.shape == (3, 6, (k - k2) // 2)
        np.testing.assert_array_equal(unpack(head, mid, tail, k1, k2, k), src)

    # natural-order input: position j read through the zigzag map
    nat = np.zeros((2, 3, 64), dtype=np.int16)
    nat[:] = rng.randint(-8, 8, (2, 3, 64))
    zz = np.asarray(ZIGZAG)
    nat_view = nat[..., zz]
    res = native.jpeg_pack_split_native(nat, 0, 0)
    assert res is not None
    np.testing.assert_array_equal(unpack(*res, 0, 0, 64), nat_view)

    # per-tier range failures
    base = np.zeros((1, 2, 64), dtype=np.int16)
    bad = base.copy(); bad[0, 0, 0] = 2048  # natural position 0 = zigzag 0 (head)
    assert native.jpeg_pack_split_native(bad, 8, 52) is None
    src = np.zeros((1, 2, 64), dtype=np.int16); src[..., 20] = 128
    assert native.jpeg_pack_split_native(src, 0, 64, is_zigzag=True) is None
    src = np.zeros((1, 2, 64), dtype=np.int16); src[..., 60] = 8
    assert native.jpeg_pack_split_native(src, 0, 0, is_zigzag=True) is None

    with pytest.raises(ValueError, match="even"):
        native.jpeg_pack_split_native(np.zeros((1, 1, 16), np.int16), 3, 8,
                                      is_zigzag=True)
    with pytest.raises(ValueError, match="k1"):
        native.jpeg_pack_split_native(np.zeros((1, 1, 16), np.int16), 12, 8,
                                      is_zigzag=True)


def test_split_pack_device_bitexact_and_sticky_growth():
    """End-to-end spectral split: decode through _decode_group must be bit-equal to
    the raw (no-narrowing) device path, and the per-layout sticky split points only
    ever grow when later batches carry wider spectra."""
    from petastorm_tpu.ops import jpeg as j
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(33)
    # smooth batch first: narrow ranges -> small split points
    smooth_blobs = []
    for _ in range(4):
        img = cv2.GaussianBlur(rng.randint(0, 256, (40, 56, 3)).astype(np.float32),
                               (9, 9), 3.0).clip(0, 255).astype(np.uint8)
        ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 85])
        smooth_blobs.append(enc.tobytes())
    # sharp batch second: same layout, wider spectra
    sharp_blobs = []
    for _ in range(4):
        img = rng.randint(0, 256, (40, 56, 3)).astype(np.uint8)
        ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 95])
        sharp_blobs.append(enc.tobytes())

    smooth = j.entropy_decode_jpeg_batch(smooth_blobs)
    sharp = j.entropy_decode_jpeg_batch(sharp_blobs)
    assert smooth[0].specmax is not None and smooth[0].specmax.shape[1] == 64
    assert smooth[0].specmax is smooth[2].specmax  # shared across the row group
    layout = j._layout_key(smooth[0])

    def raw(group):
        c, q = j.stack_jpeg_coefficients(group)
        return np.asarray(j._batched_stage2(layout)(c, q))

    out_smooth = np.asarray(j._decode_group(layout, smooth))
    np.testing.assert_array_equal(out_smooth, raw(smooth))
    with j._STICKY_KS_LOCK:
        first = list(j._STICKY_SPLIT[layout])

    out_sharp = np.asarray(j._decode_group(layout, sharp))
    np.testing.assert_array_equal(out_sharp, raw(sharp))
    with j._STICKY_KS_LOCK:
        second = list(j._STICKY_SPLIT[layout])
    for (a1, a2), (b1, b2) in zip(first, second):
        assert b1 >= a1 and b2 >= a2  # sticky: only ever grows

    # a mixed-provenance group (rows from both parents) combines profiles and
    # still decodes bit-equal
    mixed = [smooth[0], sharp[1], smooth[3], sharp[2]]
    np.testing.assert_array_equal(np.asarray(j._decode_group(layout, mixed)),
                                  raw(mixed))

    # a group containing a PROFILE-LESS row (per-image fallback decode) must not
    # forfeit the split: the batch-level specmax pass recovers it, bit-equal
    loner = j.entropy_decode_jpeg_fast(smooth_blobs[1])
    # a native per-image decode carries its own profile; a row without one (the
    # pure-Python oracle's) is made here
    assert loner.specmax is not None and loner.specmax.shape == (3, 64)
    loner = dataclasses.replace(loner, specmax=None, kmax=None)
    with_loner = [smooth[0], loner, sharp[3]]
    with j._STICKY_KS_LOCK:
        j._STICKY_SPLIT.pop(layout, None)  # force a fresh split decision
    out = np.asarray(j._decode_group(layout, with_loner))
    np.testing.assert_array_equal(out, raw(with_loner))
    with j._STICKY_KS_LOCK:
        assert layout in j._STICKY_SPLIT  # split engaged despite the loner


def test_specmax_survives_detach_and_pickle():
    """detach() and pickling keep the spectral profile, so shuffling-buffer
    stragglers and process-pool rows still ride the split pack."""
    import pickle

    from petastorm_tpu.ops import jpeg as j
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(34)
    ok, enc = cv2.imencode(".jpg", rng.randint(0, 256, (24, 24, 3), dtype=np.uint8),
                           [cv2.IMWRITE_JPEG_QUALITY, 85])
    row = j.entropy_decode_jpeg_batch([enc.tobytes()])[0]
    assert row.specmax is not None
    det = row.detach()
    assert det.batch_ref is None and det.specmax is row.specmax
    back = pickle.loads(pickle.dumps(row))
    assert back.batch_ref is None
    np.testing.assert_array_equal(back.specmax, row.specmax)


def test_pack_overflow_sticky_fallback_still_exact():
    """A component that overflows its pack tier falls down the chain (spectral split
    → 12-bit pack → int16 transfer) — output bit-equal at every tier — and each
    disablement is sticky per (layout, component)."""
    from petastorm_tpu.ops import jpeg as j
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(9)
    blobs = []
    for _ in range(4):
        img = cv2.GaussianBlur(rng.randint(0, 256, (32, 48, 3)).astype(np.float32),
                               (5, 5), 1.5).clip(0, 255).astype(np.uint8)
        ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 85])
        blobs.append(enc.tobytes())
    batch = j.entropy_decode_jpeg_batch(blobs)
    ref = np.asarray(j.decode_jpeg_batch(batch))  # full-tier path (normal content)

    layout = j._layout_key(batch[0])
    orig_pack = native.jpeg_pack_runs_native

    def overflowing(*failing):
        def pack(runs, tiers, threads=1):
            out = orig_pack(runs, tiers, threads)
            return [None if t is not None and ("split" if t[1] is not None else
                                               "pack12" if t[2] else "int16") in failing
                    else o for o, t in zip(out, tiers)]
        return pack

    try:
        # force split 'overflow': must fall back to pack12 bit-equal + sticky-disable
        native.jpeg_pack_runs_native = overflowing("split")
        out = np.asarray(j.decode_jpeg_batch(batch))
        np.testing.assert_array_equal(out, ref)
        with j._STICKY_KS_LOCK:
            assert any(key[0] == layout for key in j._SPLIT_DISABLED)
        # force pack12 'overflow' too: int16 fallback bit-equal + sticky-disable
        native.jpeg_pack_runs_native = overflowing("split", "pack12")
        out = np.asarray(j.decode_jpeg_batch(batch))
        np.testing.assert_array_equal(out, ref)
        with j._STICKY_KS_LOCK:
            assert any(key[0] == layout for key in j._PACK12_DISABLED)
    finally:
        native.jpeg_pack_runs_native = orig_pack
        with j._STICKY_KS_LOCK:
            j._PACK12_DISABLED.clear()  # don't leak the forced state to other tests
            j._SPLIT_DISABLED.clear()


def test_transfer_byte_counters_track_realized_narrowing():
    """The cumulative shipped/raw accounting must grow with decode work and show a
    genuine reduction on content the narrowing helps."""
    from petastorm_tpu.ops import jpeg as j
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(41)
    blobs = []
    for _ in range(6):
        img = cv2.GaussianBlur(rng.randint(0, 256, (40, 56, 3)).astype(np.float32),
                               (9, 9), 3.0).clip(0, 255).astype(np.uint8)
        ok, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 85])
        blobs.append(enc.tobytes())
    batch = j.entropy_decode_jpeg_batch(blobs)
    before = j.transfer_byte_counters(reset=True)
    assert j.transfer_byte_counters() == {"shipped": 0, "raw": 0}
    np.asarray(j.decode_jpeg_batch(batch))
    after = j.transfer_byte_counters()
    assert after["raw"] > 0
    assert 0 < after["shipped"] < after["raw"]  # narrowing engaged
    # raw equals the full int16 coefficient footprint for the batch
    expected_raw = sum(c.blocks.size * 2 for p in batch for c in p.components)
    assert after["raw"] == expected_raw


# -- stage-2's input packed straight from the row groups' buffers ----------------------

#: synthetic layout: 4:2:0 over 4x4 luma blocks (the packer reads only the block
#: counts); its own image size keeps its sticky variant state apart from others
_PACK_LAYOUT = (24, 40, ((2, 2, 4, 4), (1, 1, 2, 2), (1, 1, 2, 2)))
#: per zigzag position, the largest |coefficient| drawn: 12-bit head, int8 mid,
#: 4-bit tail, so the spectral split takes all three tiers
_PACK_LIMITS = np.array([1500] * 4 + [100] * 20 + [7] * 40)


def _pack_parent(rng, rows, kmax, dc=None, specmax_cap=None):
    """A synthetic row-group batch decode: stacked coefficient buffers holding
    nonzeros only up to ``kmax`` (zigzag) per component, the planes that view them,
    and the group's profile (``specmax_cap`` understates it, as mixed-provenance
    rows can; ``dc`` writes one out-of-12-bit DC coefficient)."""
    from petastorm_tpu.ops import native
    from petastorm_tpu.ops.jpeg import ZIGZAG, JpegComponent, JpegPlanes

    height, width, comps = _PACK_LAYOUT
    coeffs = []
    for (_h, _v, by, bx), km in zip(comps, kmax):
        a = np.zeros((rows, by * bx, 64), np.int16)
        lim = _PACK_LIMITS[:km + 1]
        a[..., ZIGZAG[:km + 1]] = rng.randint(-lim, lim + 1, (rows, by * bx, km + 1))
        coeffs.append(a)
    if dc is not None:
        coeffs[0][rows // 2, 1, 0] = dc
    coeffs = tuple(coeffs)
    qtabs = rng.randint(1, 100, (rows, len(comps), 64)).astype(np.int32)
    specmax = np.stack([native.jpeg_specmax_native(c) for c in coeffs])
    if specmax_cap is not None:
        specmax = np.minimum(specmax, specmax_cap)
    return [JpegPlanes(height, width,
                       [JpegComponent(coeffs[c][i].reshape(by, bx, 64), qtabs[i, c], h, v)
                        for c, (h, v, by, bx) in enumerate(comps)],
                       batch_ref=(coeffs, qtabs, i), kmax=tuple(kmax), specmax=specmax)
            for i in range(rows)]


def _lone(row, profiled=False):
    """``row`` as a per-image fallback decode gives it: its own blocks, no
    ``batch_ref`` (and, unless ``profiled``, no ``kmax`` or profile)."""
    d = row.detach()
    if not profiled:
        d.kmax = d.specmax = None
    return d


def _stacked_pack(group, variant):
    """What the stage-2 variant ships, composed as before the one-pass packer:
    stack, zigzag-truncate, then split, 12-bit or int16."""
    from petastorm_tpu.ops import native
    from petastorm_tpu.ops.jpeg import stack_jpeg_coefficients

    ks, packed, split = variant
    coeffs, qtabs = stack_jpeg_coefficients(group)
    out = []
    for ci, c in enumerate(coeffs):
        k = ks[ci] if ks is not None else 64
        if k < 64:
            c = native.jpeg_zigzag_truncate_native(c, k)
        if split[ci] is not None:
            out.append(native.jpeg_pack_split_native(c, *split[ci], is_zigzag=k < 64))
        elif packed[ci]:
            out.append(native.jpeg_pack12_native(c))
        else:
            out.append(np.ascontiguousarray(c, dtype=np.int16))
    return out, qtabs


def _pack_case(case, rng):
    """``(group, expected ks, component the fallbacks hit, expected tier there)``."""
    full = (63, 63, 63)
    p = [_pack_parent(rng, 20, full) for _ in range(3)]
    if case == "one_parent_consecutive":
        return p[0][2:18], None, None, None
    if case == "one_parent_gathered":
        return [p[0][i] for i in (7, 3, 19, 0, 12, 4, 5, 6, 18, 1)], None, None, None
    if case == "two_parents":
        return p[0][12:] + p[1][:10], None, None, None
    if case == "three_parents":
        return p[0][15:] + p[1] + p[2][:5], None, None, None
    if case == "fallback_rows":
        return p[0][:6] + [_lone(p[1][3]), _lone(p[1][9])] + p[0][6:9], None, None, None
    if case == "ks16":
        a, b = _pack_parent(rng, 20, (15, 9, 9)), _pack_parent(rng, 20, (13, 11, 7))
        return a[10:] + b[:8], (16, 16, 16), None, None
    if case == "ks32_lone_profiled":
        a = _pack_parent(rng, 20, (31, 17, 20))
        return a[:9] + [_lone(a[15], profiled=True)] + a[9:12], (32, 32, 32), None, None
    if case.startswith("split_fails_to_pack12"):
        # the profile claims Cb's tail fits 4 bits; row values say otherwise
        cap = np.full((3, 64), 10 ** 4)
        cap[1, 8:] = 7
        kmax, ks = ((31, 31, 31), (32, 32, 32)) if case.endswith("ks32") else (full, None)
        a = _pack_parent(rng, 20, kmax, specmax_cap=cap)
        return a[4:16], ks, 1, "pack12"
    if case.startswith("pack12_overflows_to_int16"):
        kmax, ks = ((15, 9, 9), (16, 16, 16)) if case.endswith("ks16") else (full, None)
        a = _pack_parent(rng, 20, kmax, dc=3000)
        return a[5:15] + _pack_parent(rng, 6, kmax), ks, 0, "int16"
    raise AssertionError(case)


@pytest.mark.parametrize("threads", [1, 5])
@pytest.mark.parametrize("case", [
    "one_parent_consecutive", "one_parent_gathered", "two_parents", "three_parents",
    "fallback_rows", "ks16", "ks32_lone_profiled", "split_fails_to_pack12",
    "split_fails_to_pack12_ks32", "pack12_overflows_to_int16",
    "pack12_overflows_to_int16_ks16"])
def test_one_pass_pack_matches_stacked_composition(case, threads, monkeypatch):
    """The one-pass packer ships byte for byte what stacking the rows, truncating
    the zigzag prefix and narrowing (split, 12-bit or int16) ships, with the same
    qtabs and variant, over one thread or several, for rows of one, two or three
    row groups in or out of order and lone per-image rows; a range failure falls
    down the same chain (split, then 12-bit, then int16), sticky."""
    from petastorm_tpu.ops import jpeg as j
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    group, ks, fault_comp, fault_tier = _pack_case(case, np.random.RandomState(51))
    layout = _PACK_LAYOUT
    monkeypatch.setattr(j, "_pack_threads", lambda n: threads)

    def forget():
        with j._STICKY_KS_LOCK:
            j._STICKY_KS.pop(layout, None)
            j._STICKY_SPLIT.pop(layout, None)
            for ci in range(3):
                j._SPLIT_DISABLED.discard((layout, ci))
                j._PACK12_DISABLED.discard((layout, ci))

    forget()
    try:
        variant, shipped, qtabs, nbytes = j._pack_group(layout, group)
        with j._STICKY_KS_LOCK:
            split_off = {ci for ci in range(3) if (layout, ci) in j._SPLIT_DISABLED}
            pack12_off = {ci for ci in range(3) if (layout, ci) in j._PACK12_DISABLED}
    finally:
        forget()
    ks_got, packed, split = variant
    assert ks_got == ks
    for ci in range(3):
        tier = "split" if split[ci] is not None else "pack12" if packed[ci] else "int16"
        assert tier == (fault_tier if ci == fault_comp else "split"), (ci, variant)
    assert split_off == ({fault_comp} if fault_comp is not None else set())
    assert pack12_off == ({fault_comp} if fault_tier == "int16" else set())

    want, want_q = _stacked_pack(group, variant)
    for got, exp in zip(shipped, want):
        for g, e in zip(got if isinstance(got, tuple) else (got,),
                        exp if isinstance(exp, tuple) else (exp,)):
            assert g.dtype == e.dtype and g.shape == e.shape
            np.testing.assert_array_equal(g, e)
    for g, e in zip(qtabs, want_q):
        assert g.dtype == e.dtype
        np.testing.assert_array_equal(g, e)
    assert nbytes == sum(a.nbytes for s in want
                         for a in (s if isinstance(s, tuple) else (s,)))


def test_decode_across_two_row_groups_bit_equal():
    """A batch that crosses from one row group's buffers into the next decodes on
    the one-pass path exactly as the un-narrowed stacked transfer does."""
    from petastorm_tpu.ops import jpeg as j
    from petastorm_tpu.ops import native

    if not native.native_available():
        pytest.skip("native toolchain unavailable: %s" % native.native_error())
    rng = np.random.RandomState(52)
    blobs = []
    for _ in range(10):
        img = rng.randint(0, 256, (32, 40, 3)).astype(np.float32)
        img = 0.5 * cv2.GaussianBlur(img, (5, 5), 1.5) + 0.5 * img
        ok, enc = cv2.imencode(".jpg", img.clip(0, 255).astype(np.uint8),
                               [cv2.IMWRITE_JPEG_QUALITY, 85])
        blobs.append(enc.tobytes())
    first, second = j.entropy_decode_jpeg_batch(blobs[:6]), \
        j.entropy_decode_jpeg_batch(blobs[6:])
    batch = first[2:] + second
    assert len(j._source_runs(batch)) == 2
    layout = j._layout_key(batch[0])
    coeffs, qtabs = j.stack_jpeg_coefficients(batch)
    raw = np.asarray(j._batched_stage2(layout)(coeffs, qtabs))
    np.testing.assert_array_equal(np.asarray(j.decode_jpeg_batch(batch)), raw)
