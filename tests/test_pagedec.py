"""Compressed-page pass-through (ISSUE 14): walker/classifier units, the
numpy reference twin's byte-identity vs pyarrow (incl. seeded fuzz corpora
across codec x encoding x null density), the corruption gate
(``pagedec_corrupt`` classified, never out-of-bounds), the device inflate
stage, and the pass-through seam itself (mixed eligibility, lease
accounting, chaos at ``io.pagedec``, attribution of ``decode.device_inflate``,
pool-child control frames)."""
import io
import os
import struct

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from petastorm_tpu import chaos
from petastorm_tpu.chaos import FaultPlan, FaultRule
from petastorm_tpu.errors import PagedecCorruptError
from petastorm_tpu.io import IoOptions, pagedec
from petastorm_tpu.loader import DataLoader
from petastorm_tpu.obs.metrics import default_registry
from petastorm_tpu.reader import make_batch_reader


@pytest.fixture(autouse=True)
def _disarm():
    chaos.disarm()
    yield
    chaos.disarm()


def _leaked_total():
    return default_registry().counter("ptpu_lease_leaked_total").value


def _write(table, compression="snappy", row_group_size=2000, **kwargs):
    buf = io.BytesIO()
    pq.write_table(table, buf, compression=compression,
                   row_group_size=row_group_size, **kwargs)
    return buf.getvalue()


def _chunk_bytes(data, md, rg, col_idx):
    col = md.row_group(rg).column(col_idx)
    start = col.data_page_offset
    if col.dictionary_page_offset is not None:
        start = min(start, col.dictionary_page_offset)
    return data[start:start + col.total_compressed_size]


def _build(data, md, rg, col_idx, require_saving=False):
    el = pagedec.classify_chunk(md, rg, col_idx)
    assert el.eligible, el.reason
    chunk, reason = pagedec.build_chunk(
        _chunk_bytes(data, md, rg, col_idx), el,
        expected_values=md.row_group(rg).num_rows,
        require_saving=require_saving)
    assert chunk is not None, reason
    return chunk


def _simple_table(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "f": pa.array(np.repeat(rng.normal(size=max(1, n // 50))
                                .astype(np.float32), 50)[:n]),
        "cat": pa.array(rng.integers(0, 11, size=n).astype(np.int64)),
        "i": pa.array(rng.integers(-1000, 1000, size=n).astype(np.int32)),
    })


# -- walker / classifier units ----------------------------------------------------------


def test_walk_pages_classifies_dict_and_data_pages():
    t = _simple_table()
    data = _write(t, data_page_size=2048)
    md = pq.read_metadata(io.BytesIO(data))
    raw = _chunk_bytes(data, md, 0, 0)
    dict_page, pages = pagedec.walk_pages(raw, md.row_group(0).num_rows)
    assert dict_page is not None and dict_page.kind == pagedec.PAGE_DICT
    assert pages and all(p.kind == pagedec.PAGE_DATA for p in pages)
    assert sum(p.num_values for p in pages) == md.row_group(0).num_rows
    assert all(p.encoding in (pagedec.ENC_PLAIN_DICT, pagedec.ENC_RLE_DICT,
                              pagedec.ENC_PLAIN) for p in pages)


def test_walk_pages_value_total_mismatch_is_corrupt():
    t = _simple_table()
    data = _write(t)
    md = pq.read_metadata(io.BytesIO(data))
    raw = _chunk_bytes(data, md, 0, 0)
    with pytest.raises(PagedecCorruptError):
        pagedec.walk_pages(raw, md.row_group(0).num_rows + 1)


def test_walk_truncated_chunk_raises_classified():
    t = _simple_table()
    data = _write(t)
    md = pq.read_metadata(io.BytesIO(data))
    raw = _chunk_bytes(data, md, 0, 0)
    for cut in (1, 3, len(raw) // 2, len(raw) - 1):
        with pytest.raises(PagedecCorruptError):
            pagedec.walk_pages(raw[:cut], md.row_group(0).num_rows)


def test_classifier_footer_gates():
    rng = np.random.default_rng(3)
    n = 500
    vals = rng.normal(size=n).astype(np.float32)
    nulls = vals.copy().astype(object)
    nulls[7] = None
    t = pa.table({
        "ok": pa.array(vals),
        "s": pa.array(["x%d" % i for i in range(n)]),      # byte array
        "nested": pa.array([{"a": int(i)} for i in range(n)]),
        "withnull": pa.array(list(nulls), type=pa.float32()),
    })
    data = _write(t)
    md = pq.read_metadata(io.BytesIO(data))
    verdicts = {}
    for i in range(md.row_group(0).num_columns):
        name = md.row_group(0).column(i).path_in_schema
        verdicts[name] = pagedec.classify_chunk(md, 0, i)
    assert verdicts["ok"].eligible
    assert not verdicts["s"].eligible
    assert "physical type" in verdicts["s"].reason
    nested = [v for k, v in verdicts.items() if k.startswith("nested")]
    assert nested and not nested[0].eligible
    assert not verdicts["withnull"].eligible
    assert "null" in verdicts["withnull"].reason


def test_classifier_codec_gates():
    t = _simple_table(400)
    for codec, eligible, fragment in (
            ("gzip", False, "unsupported codec"),
            ("zstd", False, "no device kernel"),
            ("none", True, ""),
            ("snappy", True, "")):
        data = _write(t, compression=codec)
        md = pq.read_metadata(io.BytesIO(data))
        el = pagedec.classify_chunk(md, 0, 0)
        assert el.eligible == eligible, (codec, el.reason)
        if fragment:
            assert fragment in el.reason


def test_codec_ineligible_counted_and_degraded():
    """ISSUE 19 satellite: a classified-but-kernel-less codec (zstd) must not
    silently take the classic read — every locked-out column bumps the
    labeled counter and the cause is recorded once."""
    t = _simple_table(400)
    counter = default_registry().counter(
        "ptpu_pagedec_codec_ineligible_columns_total", codec="zstd")
    cause = default_registry().counter(
        "ptpu_degradations_total", cause="pagedec_codec_ineligible{codec=zstd}")
    before = counter.value
    deg_before = cause.value

    data = _write(t, compression="zstd")
    md = pq.read_metadata(io.BytesIO(data))
    for c in range(md.num_columns):
        el = pagedec.classify_chunk(md, 0, c)
        assert not el.eligible and "no device kernel" in el.reason
    assert counter.value - before == md.num_columns
    assert cause.value - deg_before == md.num_columns

    # an UNKNOWN codec (gzip) is a plain rejection, not a kernel gap — it
    # must not inflate the zstd lockout accounting
    mid = counter.value
    md2 = pq.read_metadata(io.BytesIO(_write(t, compression="gzip")))
    assert not pagedec.classify_chunk(md2, 0, 0).eligible
    assert counter.value == mid


def test_no_saving_gate_degrades_incompressible_chunks():
    # pure float noise dictionary-encodes BIGGER than raw — pass-through
    # must decline (shipping more bytes than raw helps nobody)
    rng = np.random.default_rng(9)
    t = pa.table({"noise": pa.array(rng.normal(size=3000).astype(np.float32))})
    data = _write(t)
    md = pq.read_metadata(io.BytesIO(data))
    el = pagedec.classify_chunk(md, 0, 0)
    assert el.eligible
    chunk, reason = pagedec.build_chunk(
        _chunk_bytes(data, md, 0, 0), el,
        expected_values=md.row_group(0).num_rows)
    assert chunk is None and "no byte saving" in reason


# -- RLE/bit-packed + reference decode --------------------------------------------------


def test_rle_bp_decode_bounds():
    with pytest.raises(PagedecCorruptError):
        pagedec.rle_bp_decode(b"", 4, 10)  # empty stream, values owed
    with pytest.raises(PagedecCorruptError):
        pagedec.rle_bp_decode(b"\x03", 4, 10)  # bit-packed run past end
    # zero-length RLE run is corrupt, not an infinite loop
    with pytest.raises(PagedecCorruptError):
        pagedec.rle_bp_decode(b"\x00\x01", 4, 10)


def test_rle_bp_decode_mixed_runs():
    # RLE run of 9 zeros (header 9<<1, value byte 0) then a bit-packed group
    # of 8 values at bit width 4
    packed = bytes([0x10, 0x32, 0x54, 0x76])  # 0,1,2,3,4,5,6,7
    buf = bytes([9 << 1, 0x00, (1 << 1) | 1]) + packed
    out = pagedec.rle_bp_decode(buf, 4, 17)
    assert list(out) == [0] * 9 + list(range(8))


def test_reference_decode_identity_simple():
    t = _simple_table()
    data = _write(t, data_page_size=2048)
    md = pq.read_metadata(io.BytesIO(data))
    table = pq.read_table(io.BytesIO(data))
    off = 0
    for rg in range(md.num_row_groups):
        nrows = md.row_group(rg).num_rows
        for c in range(md.row_group(rg).num_columns):
            chunk = _build(data, md, rg, c)
            want = table.column(c).to_numpy()[off:off + nrows]
            got = chunk.decode()
            assert got.dtype == want.dtype
            assert np.array_equal(got, want)
        off += nrows


@pytest.mark.parametrize("codec", ["snappy", "none"])
@pytest.mark.parametrize("dict_limit", [64, 1 << 20])
def test_reference_decode_fuzz_corpora(codec, dict_limit):
    """Seeded fuzz across codec x encoding (dictionary vs PLAIN-fallback via a
    tiny dictionary-page limit) x dtype x distribution: byte-identity vs
    pyarrow on every chunk that passes classification."""
    rng = np.random.default_rng(hash((codec, dict_limit)) & 0xFFFF)
    for trial in range(4):
        n = int(rng.integers(1, 4000))
        cols = {
            "a": rng.integers(0, max(2, int(rng.integers(2, 5000))),
                              size=n).astype(np.int64),
            "b": np.repeat(rng.normal(size=max(1, n // 7 + 1)), 7)[:n]
            .astype(np.float32),
            "c": rng.integers(-5, 5, size=n).astype(np.int32),
            "d": np.repeat(rng.normal(size=max(1, n // 3 + 1)), 3)[:n]
            .astype(np.float64),
        }
        t = pa.table({k: pa.array(v) for k, v in cols.items()})
        data = _write(t, compression=codec,
                      row_group_size=int(rng.integers(200, 2200)),
                      data_page_size=int(rng.integers(512, 8192)),
                      dictionary_pagesize_limit=dict_limit)
        md = pq.read_metadata(io.BytesIO(data))
        table = pq.read_table(io.BytesIO(data))
        off = 0
        for rg in range(md.num_row_groups):
            nrows = md.row_group(rg).num_rows
            for c in range(md.row_group(rg).num_columns):
                el = pagedec.classify_chunk(md, rg, c)
                assert el.eligible, el.reason
                chunk, _reason = pagedec.build_chunk(
                    _chunk_bytes(data, md, rg, c), el,
                    expected_values=nrows, require_saving=False)
                if chunk is None:
                    continue  # e.g. an unexpected encoding: fallback, not a bug
                want = table.column(c).to_numpy()[off:off + nrows]
                assert np.array_equal(chunk.decode(), want), (trial, rg, c)
            off += nrows


def test_null_density_corpus_classifies_ineligible():
    """Columns with actual nulls (any density) must NEVER classify eligible —
    the decoders assume null-freedom proved by statistics."""
    rng = np.random.default_rng(5)
    for density in (0.01, 0.3, 0.9):
        vals = rng.normal(size=800).astype(np.float64)
        mask = rng.random(800) < density
        arr = pa.array([None if m else float(v) for m, v in zip(mask, vals)],
                       type=pa.float64())
        data = _write(pa.table({"x": arr}))
        md = pq.read_metadata(io.BytesIO(data))
        el = pagedec.classify_chunk(md, 0, 0)
        if mask.any():
            assert not el.eligible
        else:  # density so low no null landed: eligible is correct
            assert el.eligible


def test_corruption_gate_bit_flips_never_read_oob():
    """Flip bytes everywhere in the chunk: every outcome must be either a
    classified PagedecCorruptError or a well-formed array (a value-level flip
    snappy cannot detect) — never any other exception, never OOB."""
    t = _simple_table(1200)
    data = _write(t, data_page_size=1024)
    md = pq.read_metadata(io.BytesIO(data))
    raw = _chunk_bytes(data, md, 0, 1)  # cat: dict + RLE indices
    el = pagedec.classify_chunk(md, 0, 1)
    nrows = md.row_group(0).num_rows
    rng = np.random.default_rng(17)
    outcomes = {"corrupt": 0, "clean": 0, "ineligible": 0}
    for _ in range(80):
        pos = int(rng.integers(0, len(raw)))
        bit = 1 << int(rng.integers(0, 8))
        flipped = bytearray(raw)
        flipped[pos] ^= bit
        try:
            chunk, _ = pagedec.build_chunk(bytes(flipped), el,
                                           expected_values=nrows,
                                           require_saving=False)
            if chunk is None:
                outcomes["ineligible"] += 1
                continue
            out = chunk.decode()
            assert len(out) == nrows
            outcomes["clean"] += 1
        except PagedecCorruptError:
            outcomes["corrupt"] += 1
    # the gate must actually trip on a meaningful share of flips
    assert outcomes["corrupt"] > 10, outcomes


def test_truncated_pages_raise_classified_at_decode():
    t = _simple_table(1500)
    data = _write(t)
    md = pq.read_metadata(io.BytesIO(data))
    raw = bytearray(_chunk_bytes(data, md, 0, 0))
    el = pagedec.classify_chunk(md, 0, 0)
    chunk, _ = pagedec.build_chunk(bytes(raw), el,
                                   expected_values=md.row_group(0).num_rows,
                                   require_saving=False)
    # corrupt the SNAPPY payload of the first data page (past its header)
    page = chunk.pages[0]
    raw[page.payload_offset + 2] ^= 0xFF
    bad, _ = pagedec.build_chunk(bytes(raw), el,
                                 expected_values=md.row_group(0).num_rows,
                                 require_saving=False)
    if bad is not None:
        with pytest.raises(PagedecCorruptError):
            bad.decode()


# -- PassthroughColumn ------------------------------------------------------------------


def _one_chunk():
    t = _simple_table(2600)
    data = _write(t, data_page_size=2048)
    md = pq.read_metadata(io.BytesIO(data))
    table = pq.read_table(io.BytesIO(data))
    chunk = _build(data, md, 0, 1)
    return chunk, table.column("cat").to_numpy()[:md.row_group(0).num_rows]


def test_passthrough_column_slice_concat_pickle():
    import pickle

    chunk, want = _one_chunk()
    col = pagedec.PassthroughColumn.from_chunk(chunk)
    assert len(col) == len(want)
    assert np.array_equal(col.materialize(), want)
    s = col[100:700]
    assert len(s) == 600
    assert np.array_equal(s.materialize(), want[100:700])
    s2 = s.slice(10, 50)
    assert np.array_equal(s2.materialize(), want[110:160])
    cat = pagedec.PassthroughColumn.concat([s, s2])
    assert np.array_equal(cat.materialize(),
                          np.concatenate([want[100:700], want[110:160]]))
    rt = pickle.loads(pickle.dumps(cat))
    assert np.array_equal(rt.materialize(), cat.materialize())
    assert col.shipped_nbytes <= col.nbytes + 16 * (len(chunk.pages) + 1)
    assert col.detach() is col
    with pytest.raises(TypeError):
        col[5]
    with pytest.raises(IndexError):
        col.slice(0, len(col) + 1)


def test_passthrough_materialize_columns_helper():
    chunk, want = _one_chunk()
    cols = {"cat": pagedec.PassthroughColumn.from_chunk(chunk),
            "plain": np.arange(len(want))}
    out = pagedec.materialize_columns(cols)
    assert np.array_equal(out["cat"], want)
    assert out["plain"] is cols["plain"]
    untouched = {"plain": np.arange(4)}
    assert pagedec.materialize_columns(untouched) is untouched


# -- device inflate stage ------------------------------------------------------------------


@pytest.mark.slow
def test_kernel_chunk_identity_vs_reference():
    from petastorm_tpu.ops import pagedec_kernels as pk

    t = _simple_table(1800)
    for codec in ("snappy", "none"):
        data = _write(t, compression=codec, data_page_size=2048)
        md = pq.read_metadata(io.BytesIO(data))
        for c in range(md.row_group(0).num_columns):
            chunk = _build(data, md, 0, c)
            want = chunk.decode()
            got = np.asarray(pk.inflate_chunk(chunk))
            # int64 canonicalizes to int32 on x64-disabled jax — by VALUE
            # truncation, matching the classic device_put delivery
            assert np.array_equal(got, want.astype(got.dtype)), (codec, c)


def test_kernel_window_slice_identity():
    from petastorm_tpu.ops import pagedec_kernels as pk

    chunk, want = _one_chunk()
    col = pagedec.PassthroughColumn.from_chunk(chunk).slice(37, 911)
    got = np.asarray(pk.inflate_column(col))
    assert np.array_equal(got, want[37:948].astype(got.dtype))


def test_kernel_corrupt_payload_latches_ok_false():
    from petastorm_tpu.ops import pagedec_kernels as pk

    t = _simple_table(900)
    data = _write(t)
    md = pq.read_metadata(io.BytesIO(data))
    chunk = _build(data, md, 0, 0)
    raw = bytearray(chunk.buf)
    page = chunk.pages[0]
    raw[page.payload_offset + 1] ^= 0x55
    bad = pagedec.PassthroughChunk(bytes(raw), chunk.codec, chunk.dtype,
                                   chunk.max_def, chunk.dict_page, chunk.pages)
    try:
        out = pk.inflate_chunk(bad)
    except pk.DeviceInflateError:
        return  # latched: the host fallback would classify it
    # an undetectable value-level flip: still well-formed output
    assert np.asarray(out).shape == (bad.num_rows,)


def test_kernel_crafted_literal_length_terminates_not_hangs():
    """Regression (review): a tag-0 literal with 4 extra length bytes whose
    top bit is set used to compute a NEGATIVE int32 length with ok still
    True — the token loop could cycle forever. The kernel must terminate
    promptly with ok=False (or a bounds-rejected short decode)."""
    from petastorm_tpu.ops import pagedec_kernels as pk

    # preamble: claimed uncompressed length 64; then tag 252 (n0=63 -> 4
    # extra bytes) with 0xFF length bytes
    comp = bytes([64]) + bytes([252, 0xFF, 0xFF, 0xFF, 0xFF]) + b"\x00" * 10
    buf = np.zeros((1, 64), np.uint8)
    buf[0, :len(comp)] = np.frombuffer(comp, np.uint8)
    meta = np.array([[len(comp), 64]], np.int32)
    out, ok = pk.snappy_inflate_pages(buf, meta, 64)
    assert not bool(np.asarray(ok)[0])


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


@pytest.mark.parametrize("tokens,n_out", [
    # literal with a 1-byte extra length (tag 60), then an overlapping copy
    # (offset 1 < length 10: run-length expansion) of the 2-byte-offset form
    ([b"\xf0\x63" + bytes(range(100)), bytes([(9 << 2) | 2, 1, 0])], 110),
    # 1-byte-offset copy (kind 1) and the 4-byte-offset form (kind 3)
    ([bytes([7 << 2]) + b"abcdefgh", bytes([1 | (3 << 2), 5]),
      bytes([(4 << 2) | 3, 3, 0, 0, 0])], 20),
    # literal with a 2-byte extra length (tag 61)
    ([b"\xf4\x2b\x01" + bytes(range(256)) + bytes(44)], 300),
], ids=["lit60-overlap", "copy1-copy4", "lit61"])
def test_snappy_decode_token_forms(tokens, n_out):
    """Hand-built streams pin every token form the device decode reads
    (pyarrow's compressor never emits some of them), against pyarrow's own
    snappy decoder."""
    from petastorm_tpu.ops import pagedec_kernels as pk

    comp = _varint(n_out) + b"".join(tokens)
    want = pa.Codec("snappy").decompress(comp, n_out, asbytes=True)
    buf = np.zeros((1, pk._bucket(len(comp))), np.uint8)
    buf[0, :len(comp)] = np.frombuffer(comp, np.uint8)
    meta = np.array([[len(comp), n_out]], np.int32)
    out, ok = pk.snappy_inflate_pages(buf, meta, pk._bucket(n_out))
    assert bool(np.asarray(ok)[0])
    assert np.asarray(out)[0, :n_out].tobytes() == want


def test_covering_pages_window_selection():
    """Window decodes touch only covering pages (the review's linearity
    fix): page math pins the selection."""
    t = _simple_table(4000)
    data = _write(t, row_group_size=4000, data_page_size=512)
    md = pq.read_metadata(io.BytesIO(data))
    chunk = _build(data, md, 0, 0)  # f: repeated floats, many small pages
    want = pq.read_table(io.BytesIO(data)).column("f").to_numpy()
    starts = chunk.page_starts()
    assert len(chunk.pages) >= 2, "fixture needs a multi-page chunk"
    p0, p1, base = chunk.covering_pages(starts[1] + 3, 5)
    assert p0 == 1 and base == starts[1]
    assert p1 == 2 or starts[p1 - 1] < starts[1] + 8
    # a one-row window at the chunk head touches exactly the first page
    p0, p1, base = chunk.covering_pages(0, 1)
    assert (p0, p1, base) == (0, 1, 0)
    assert np.array_equal(chunk.decode_window(starts[1] + 3, 5),
                          want[starts[1] + 3:starts[1] + 8])


def test_kernel_rle_expand_matches_reference():
    """The loader's own data-page program on hand-rolled dictionary pages:
    row 0 is an identity dictionary, row 1 the bit-width byte + index stream,
    so the gathered values are the expanded indices. 11 bits covers the
    bit-9 loss the Pallas extraction showed on a v5e (PR 21)."""
    import jax.numpy as jnp

    from petastorm_tpu.ops import pagedec_kernels as pk

    rng = np.random.default_rng(23)
    for bw in (1, 3, 7, 11, 12):
        # build a hybrid stream: RLE run + bit-packed groups via the writer's
        # own output (round-trip through a real page would couple this test
        # to pyarrow internals; hand-rolled streams pin OUR format reading)
        vals = []
        out = bytearray()
        run = int(rng.integers(1, 40))
        v = int(rng.integers(0, 1 << bw))
        out += bytes([run << 1]) + int(v).to_bytes((bw + 7) // 8, "little")
        vals += [v] * run
        groups = int(rng.integers(1, 5))
        packed_vals = rng.integers(0, 1 << bw, size=groups * 8)
        bits = np.unpackbits(
            packed_vals.astype("<u4").view(np.uint8).reshape(-1, 4),
            bitorder="little", axis=1)[:, :bw].ravel()
        out += bytes([(groups << 1) | 1]) + np.packbits(
            bits, bitorder="little").tobytes()
        vals += list(packed_vals)
        ref = pagedec.rle_bp_decode(bytes(out), bw, len(vals))
        assert list(ref) == vals
        dictionary = np.arange(1 << bw, dtype="<i4").view(np.uint8)
        page = np.frombuffer(bytes([bw]) + bytes(out), np.uint8)
        raw = np.zeros((2, pk._bucket(max(dictionary.size, page.size))), np.uint8)
        raw[0, :dictionary.size] = dictionary
        raw[1, :page.size] = page
        fn = pk._data_page_fn(raw.shape[1], "<i4", len(vals), bw)
        dev, ok = fn(jnp.asarray(raw), 1, 1, len(out), 1 << bw)
        assert bool(ok)
        assert list(np.asarray(dev)) == vals


def test_kernel_float64_bails_to_host_without_x64():
    import jax

    from petastorm_tpu.ops import pagedec_kernels as pk

    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: float64 inflates on device directly")
    t = pa.table({"d": pa.array(np.repeat(np.arange(40.0), 50))})
    data = _write(t)
    md = pq.read_metadata(io.BytesIO(data))
    chunk = _build(data, md, 0, 0)
    with pytest.raises(pk.DeviceInflateError):
        pk.inflate_chunk(chunk)


# -- the pass-through seam --------------------------------------------------------------


def _store(tmp_path, name="ds", n=4000, row_group_size=1000, with_string=True,
           seed=11):
    rng = np.random.default_rng(seed)
    cols = {
        "feat": pa.array(np.repeat(rng.normal(size=-(-n // 40))
                                   .astype(np.float32), 40)[:n]),
        "cat": pa.array(rng.integers(0, 13, size=n).astype(np.int64)),
        "id": pa.array(np.arange(n, dtype=np.int32)),
    }
    if with_string:
        cols["s"] = pa.array(["row-%d" % i for i in range(n)])
    root = str(tmp_path / name)
    os.makedirs(root, exist_ok=True)
    pq.write_table(pa.table(cols), os.path.join(root, "part-0.parquet"),
                   compression="snappy", row_group_size=row_group_size)
    return root


def _collect(url, pagedec_mode, to_device=False, batch=512, **reader_kwargs):
    out = []
    with make_batch_reader(url, reader_pool_type="thread", workers_count=1,
                           shuffle_row_groups=False,
                           io_options={"pagedec": pagedec_mode},
                           **reader_kwargs) as r:
        with DataLoader(r, batch, to_device=to_device,
                        last_batch="partial") as loader:
            for b in loader:
                out.append({k: np.asarray(v) for k, v in b.items()})
    return out


def _assert_batches_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert set(x) == set(y)
        for k in x:
            assert np.array_equal(x[k], y[k]), k


def test_mixed_eligibility_batch_identity(tmp_path):
    """One eligible + one ineligible (string) column in the same batch:
    delivered bytes identical to the classic path, fallback counted only for
    truly ineligible shapes (strings are footer-ineligible — not counted as
    a page-level fallback)."""
    url = "file://" + _store(tmp_path)
    _assert_batches_equal(_collect(url, "off"), _collect(url, "on"))
    # device path too (host-fallback inflate on the CPU backend)
    _assert_batches_equal(_collect(url, "off", to_device=True),
                          _collect(url, "on", to_device=True))


def test_batch_cut_across_row_groups(tmp_path):
    """Batches spanning row-group boundaries chain pass-through windows
    (PassthroughColumn.concat in _concat) and slice page-granular."""
    url = "file://" + _store(tmp_path, n=3000, row_group_size=700)
    _assert_batches_equal(_collect(url, "off", batch=997),
                          _collect(url, "on", batch=997))


def test_predicate_falls_back_whole_read(tmp_path):
    from petastorm_tpu.predicates import in_lambda

    url = "file://" + _store(tmp_path, with_string=False)
    pred = in_lambda(["id"], lambda values: values["id"] % 2 == 0,
                     vectorized_func=lambda cols: cols["id"] % 2 == 0)
    kwargs = dict(predicate=pred)
    a = _collect(url, "off", **kwargs)
    b = _collect(url, "on", **kwargs)
    _assert_batches_equal(a, b)
    assert all(np.all(x["id"] % 2 == 0) for x in b)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_pagedec_auto_stays_classic(tmp_path, monkeypatch, backend):
    """auto = off: on a CPU-only runtime host inflate is strictly cheaper
    (no PCIe link); on an accelerator the device inflate is not measured
    faster yet (PR 21), which auto says under ``pagedec_auto_off``. Either
    way no PassthroughColumn ever reaches the loader."""
    import jax

    from petastorm_tpu.obs.log import degradation_counts

    url = "file://" + _store(tmp_path, with_string=False)
    before = default_registry().counter(
        "ptpu_pagedec_bytes_compressed_total").value
    said = degradation_counts().get("pagedec_auto_off", 0)
    if backend != "cpu":  # the reader's probe only; the loader stays on host
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
    _collect(url, "auto")
    assert default_registry().counter(
        "ptpu_pagedec_bytes_compressed_total").value == before
    assert (degradation_counts().get("pagedec_auto_off", 0) > said) == \
        (backend != "cpu")


def test_loaderless_reader_materializes(tmp_path):
    url = "file://" + _store(tmp_path, with_string=False)
    with make_batch_reader(url, reader_pool_type="thread", workers_count=1,
                           shuffle_row_groups=False,
                           io_options={"pagedec": "on"}) as r:
        b = next(iter(r))
        assert isinstance(b.feat, np.ndarray) and b.feat.dtype == np.float32
        assert isinstance(b.cat, np.ndarray) and b.cat.dtype == np.int64


@pytest.mark.slow
def test_lease_accounting_and_copy_census(tmp_path):
    """shm-view process pool with pass-through on: zero leaked leases, and
    the pass-through columns add no loader-side host copies (the census
    sites that copy column payloads stay at the classic run's level)."""
    import gc

    url = "file://" + _store(tmp_path, n=2000, row_group_size=500,
                             with_string=False)
    reg = default_registry()

    def census():
        snap = reg.snapshot()
        return sum(v for k, v in snap.items()
                   if k.startswith("ptpu_copy_bytes_total"))

    def run(mode):
        leaked0 = _leaked_total()
        copies0 = census()
        out = []
        with make_batch_reader(url, reader_pool_type="process",
                               workers_count=2, shuffle_row_groups=False,
                               wire_serializer="shm-view",
                               io_options={"pagedec": mode}) as r:
            with DataLoader(r, 250, to_device=False) as loader:
                for b in loader:
                    out.append({k: np.asarray(v) for k, v in b.items()})
        gc.collect()
        assert _leaked_total() - leaked0 == 0, mode
        return out, census() - copies0

    classic, classic_copies = run("off")
    passed, passed_copies = run("on")
    key = lambda batches, k: np.sort(np.concatenate(  # noqa: E731
        [b[k] for b in batches]), kind="stable")
    for k in classic[0]:
        assert np.array_equal(key(classic, k), key(passed, k)), k
    # pass-through columns ride as owned bytes: no extra copy-census bytes
    assert passed_copies <= classic_copies


def test_chaos_at_io_pagedec_exactly_once_or_quarantined(tmp_path):
    """Transient chaos at the new io.pagedec hook site: retried like any
    read; permanent corruption quarantines; delivered ∪ quarantined == plan
    and delivery is duplicate-free."""
    url = "file://" + _store(tmp_path, n=1600, row_group_size=200,
                             with_string=False)
    plan = FaultPlan([FaultRule("io.pagedec", "raise_transient", nth=2,
                                every=3, times=2)], seed=7)
    chaos.arm(plan, propagate=False)
    try:
        with make_batch_reader(url, reader_pool_type="thread",
                               workers_count=2, shuffle_row_groups=False,
                               io_options={"pagedec": "on"},
                               recovery={"io_retries": 3,
                                         "on_poison": "quarantine"}) as r:
            ids = []
            with DataLoader(r, 100, to_device=False) as loader:
                for b in loader:
                    ids.extend(int(v) for v in np.asarray(b["id"]))
            report = r.quarantine_report
    finally:
        chaos.disarm()
    quarantined_rows = sum(q.num_rows for q in report)
    assert len(ids) == len(set(ids))
    assert len(ids) + quarantined_rows == 1600
    assert plan.injections()


def test_pagedec_corrupt_quarantines(tmp_path):
    """A truncated column chunk on disk raises the classified permanent
    error and the poison policy quarantines the row group (never burned as
    transient retries)."""
    root = _store(tmp_path, n=900, row_group_size=300, with_string=False)
    path = os.path.join(root, "part-0.parquet")
    data = open(path, "rb").read()
    md = pq.read_metadata(io.BytesIO(data))
    col = md.row_group(1).column(0)
    start = col.dictionary_page_offset or col.data_page_offset
    # stomp the middle row group's first column-chunk page headers
    corrupted = bytearray(data)
    corrupted[start:start + 16] = b"\xff" * 16
    open(path, "wb").write(bytes(corrupted))
    with make_batch_reader("file://" + root, reader_pool_type="thread",
                           workers_count=1, shuffle_row_groups=False,
                           io_options={"pagedec": "on"},
                           recovery={"io_retries": 2,
                                     "on_poison": "quarantine",
                                     "poison_attempts": 2}) as r:
        ids = []
        with DataLoader(r, 100, to_device=False) as loader:
            for b in loader:
                ids.extend(int(v) for v in np.asarray(b["id"]))
        report = r.quarantine_report
    assert report and any("pagedec" in repr(q.error).lower() for q in report)
    assert len(ids) + sum(q.num_rows for q in report) == 900


def test_attribution_names_device_inflate_when_slow(tmp_path):
    """Acceptance: a synthetic kernel-slow injection at decode.device_inflate
    makes attribution_report() name it; with the real bottleneck elsewhere
    the report must exonerate the stage. (The non-injected arm carries its
    own injected read latency: on a µs-scale pipeline the slow decile is
    trivially owned by whichever site has the most µs — the PR 13
    share-without-scale lesson — so a meaningful exoneration needs a
    genuinely dominant other site, not an idle pipeline.)"""
    url = "file://" + _store(tmp_path, n=3000, row_group_size=300,
                             with_string=False)

    def run(site):
        chaos.arm(FaultPlan([FaultRule(site, "latency", every=1,
                                       latency_s=0.05)], seed=3),
                  propagate=False)
        try:
            # readahead off: a background read that runs ahead of the worker
            # charges the injected read latency to io.readahead, and whether
            # it runs ahead depends on how busy the host is
            with make_batch_reader(url, reader_pool_type="thread",
                                   workers_count=1, shuffle_row_groups=False,
                                   io_options={"pagedec": "on",
                                               "readahead": False},
                                   provenance=True) as r:
                with DataLoader(r, 300, to_device=True) as loader:
                    for _ in loader:
                        pass
                    return loader.attribution_report()
        finally:
            chaos.disarm()

    slow = run("decode.device_inflate")
    assert slow.slow_top == "decode.device_inflate", \
        (slow.slow_top, slow.slow_share)
    clean = run("reader.read")
    assert clean.slow_top != "decode.device_inflate", clean.slow_share
    assert clean.slow_top == "reader.read", clean.slow_share


# -- knob / control-frame satellites ----------------------------------------------------


def test_ioptions_pagedec_knob_validates():
    assert IoOptions().pagedec == "auto"
    assert IoOptions(pagedec="on").pagedec == "on"
    with pytest.raises(ValueError):
        IoOptions(pagedec="sometimes")
    import pickle

    opts = pickle.loads(pickle.dumps(IoOptions(pagedec="off")))
    assert opts.pagedec == "off"


def test_build_knobset_registers_pagedec_and_process_io_knobs(tmp_path):
    from petastorm_tpu.control.knobs import build_knobset

    url = "file://" + _store(tmp_path, n=800, row_group_size=400,
                             with_string=False)
    with make_batch_reader(url, reader_pool_type="process", workers_count=1,
                           shuffle_row_groups=False, num_epochs=None,
                           io_options={"pagedec": "on"}) as r:
        ks = build_knobset(r)
        # process pools now bind the IO knobs through the control frame
        assert "readahead_depth" in ks
        assert "pagedec" in ks
        before, after = ks.apply("pagedec", "off")
        assert (before, after) == ("on", "off")
        assert ks.get("pagedec") == "off"
        ks.restore({"pagedec": "on"})
        assert ks.get("pagedec") == "on"


def test_child_control_frame_lands_without_respawn(tmp_path):
    url = "file://" + _store(tmp_path, n=2400, row_group_size=200,
                             with_string=False)
    with make_batch_reader(url, reader_pool_type="process", workers_count=2,
                           shuffle_row_groups=False, num_epochs=None,
                           io_options={"pagedec": "on"}) as r:
        it = iter(r)
        next(it)
        budget0 = r._executor._respawn_budget
        r.apply_readahead_depth(5)
        r.apply_pagedec("off")
        acks = {}
        for _ in range(20):
            next(it)
            acks = r._executor.ctl_acks()
            if any(a.get("pagedec") == "off" for a in acks.values()):
                break
        assert any(a.get("readahead_depth") == 5 for a in acks.values()), acks
        assert any(a.get("pagedec") == "off" for a in acks.values()), acks
        assert r._executor._respawn_budget == budget0  # no respawn involved


def test_controller_pagedec_rule_flips_to_host_inflate():
    from types import SimpleNamespace

    from petastorm_tpu.control import ControlOptions, Controller
    from petastorm_tpu.control.controller import default_rules
    from petastorm_tpu.control.knobs import KnobSet

    state = {"mode": "on"}
    ks = KnobSet()
    ks.enum("pagedec", get=lambda: state["mode"],
            apply_fn=lambda v: state.__setitem__("mode", v) or v,
            values=("auto", "on", "off"), default="on")
    rules = [r for r in default_rules() if r.knob == "pagedec"]
    assert rules, "pagedec rule missing from default_rules"
    report = SimpleNamespace(slow_share={"decode.device_inflate": 0.8})
    ctl = Controller(ks, rules=rules, attribution=lambda: report,
                     options=ControlOptions(warmup_windows=0,
                                            cooldown_windows=0))
    decisions = []
    for _ in range(6):
        decisions += ctl.evaluate({}, t=None)
    acts = [d for d in decisions if d.cause == "ctl_actuate"]
    assert acts and acts[0].knob == "pagedec" and acts[0].after == "off"
    assert state["mode"] == "off"


def test_remote_engine_page_granular_split(tmp_path):
    """The remote planner splits a big chunk at cached page boundaries on
    re-read (first touch: size-granular), and the raw bytes are identical
    either way."""
    from petastorm_tpu.io.latencyfs import CloudLatencyFS
    from petastorm_tpu.io.remote import RemoteIoOptions, RemoteReadEngine

    root = _store(tmp_path, n=20000, row_group_size=20000, with_string=False)
    path = os.path.join(root, "part-0.parquet")
    data = open(path, "rb").read()
    md = pq.read_metadata(io.BytesIO(data))
    import pyarrow.fs as pafs

    fs = CloudLatencyFS(pafs.LocalFileSystem(), seed=3, base_latency_s=0.0,
                        per_byte_s=0.0)
    opts = RemoteIoOptions(enabled="on", target_request_bytes=4096,
                           hedge=False)
    engine = RemoteReadEngine(fs, opts)
    try:
        pagedec.shared_page_index().clear()
        first = engine.read_raw_column_chunks(path, 0, ["feat"])
        el = pagedec.classify_chunk(md, 0, 0)
        chunk, _ = pagedec.build_chunk(first["feat"], el,
                                       expected_values=20000,
                                       require_saving=False)
        assert chunk is not None
        col = md.row_group(0).column(0)
        start = col.dictionary_page_offset or col.data_page_offset
        pagedec.shared_page_index().put(
            path, 0, "feat", start,
            [start + p.header_offset for p in chunk.pages])
        second = engine.read_raw_column_chunks(path, 0, ["feat"])
        assert second["feat"] == first["feat"]
        want = _chunk_bytes(data, md, 0, 0)
        assert first["feat"] == want
    finally:
        engine.shutdown()


def test_stats_panel_renders_pagedec_and_excludes_catch_all():
    from petastorm_tpu.obs.stats_cli import render_dashboard

    metrics = {
        "ptpu_pagedec_pages_total": 96,
        "ptpu_pagedec_bytes_compressed_total": 1_200_000,
        "ptpu_pagedec_bytes_saved_h2d_total": 2_000_000,
        "ptpu_pagedec_fallback_columns_total": 2,
        "ptpu_pagedec_inflate_seconds": {"count": 12, "p50": 0.002,
                                         "p99": 0.01, "sum": 0.03},
    }
    out = render_dashboard(metrics)
    assert "pagedec pass-through:" in out
    assert "pages=96" in out and "fallback columns=2" in out
    assert "38% of raw" in out
    assert "inflate stage:" in out
    assert "other metrics" not in out  # excluded from the catch-all


def test_pagedec_metrics_counted(tmp_path):
    url = "file://" + _store(tmp_path, with_string=False)
    reg = default_registry()
    c0 = reg.counter("ptpu_pagedec_bytes_compressed_total").value
    s0 = reg.counter("ptpu_pagedec_bytes_saved_h2d_total").value
    p0 = reg.counter("ptpu_pagedec_pages_total").value
    _collect(url, "on", to_device=True)
    assert reg.counter("ptpu_pagedec_bytes_compressed_total").value > c0
    assert reg.counter("ptpu_pagedec_bytes_saved_h2d_total").value > s0
    assert reg.counter("ptpu_pagedec_pages_total").value > p0
