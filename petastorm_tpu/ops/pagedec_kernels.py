"""Device inflate stage for the compressed-page pass-through (ISSUE 14).

The CODAG thesis (PAPERS.md) is that decompression is bandwidth-bound and
belongs on the accelerator: ship the ~storage-ratio compressed pages over
PCIe, inflate in HBM. This module is the device half of
:mod:`petastorm_tpu.io.pagedec` — same two-stage split as the JPEG path
(:mod:`petastorm_tpu.ops.jpeg`):

- :func:`snappy_inflate_pages` — snappy LZ decode as data-parallel XLA
  (token parse at every byte, pointer-jumping over the token chain and the
  copy references; pages are batched under ``vmap``); bounds violations
  latch a per-page ``ok`` flag instead of reading out of range.
- :func:`_data_page_fn` — one data page's values: PLAIN pages bitcast; RLE
  dictionary pages expand their RLE/bit-packed hybrid indices in the
  two-phase CODAG shape (a sequential run-table scan, runs ≪ values, then a
  vectorized extraction — bit-window shift + mask + RLE/packed select over
  all values at once) and gather through the inflated dictionary. The
  extraction was a Pallas kernel until PR 21: on a v5e it dropped bit 9 of
  11-bit values, so it is plain XLA now.
- :func:`inflate_column` — the loader-facing entry: a
  :class:`~petastorm_tpu.io.pagedec.PassthroughColumn` window → the decoded
  ``jax.Array`` in HBM, page tables and compressed bytes being the only H2D
  traffic.

Everything here is XLA, the same program on CPU and TPU; the numpy
reference decoders in
``io/pagedec.py`` are the bit-identity twin — any per-page ``ok=False``
(corruption, unsupported shape like bit widths over 24) falls back to the
host reference path, which validates fully and raises the classified
:class:`~petastorm_tpu.errors.PagedecCorruptError`.
"""
from __future__ import annotations

import functools

import numpy as np


# -- snappy LZ decode -----------------------------------------------------------------
#
# Format: varint uncompressed length, then tagged elements. tag & 3:
#   0 literal  (len from tag>>2, 60..63 select 1..4 extra length bytes)
#   1 copy     (len 4..11 from tag bits 2-4, offset 11 bits: tag bits 5-7 + 1 byte)
#   2 copy     (len 1..64 from tag>>2, offset 2 bytes LE)
#   3 copy     (len 1..64 from tag>>2, offset 4 bytes LE)
#
# A byte-serial token machine does not fit the TPU: Mosaic refuses a
# (1, page_bytes) uint8 block and has no dynamic per-byte gather/scatter on
# vectors. The decode is therefore data-parallel XLA over every byte of the
# page, in three pointer-jumping passes (each O(n log n) gathers):
#   1. parse a token AT EVERY source position (it only reads that position's
#      five bytes) — each position then names the next token's position;
#   2. the real tokens are the chain from the preamble's end: mark it by
#      doubling (``log2(src_cap)`` rounds), then an exclusive cumsum of their
#      output lengths places every token in the output;
#   3. every output byte is either a literal byte or a reference to an
#      earlier output byte (copy, possibly overlapping its own output):
#      ``log2(out_cap)`` rounds of pointer doubling resolve each to a literal.
# Corruption latches a per-page ``ok`` flag and every read is clamped, so a
# bad page can never read out of range; the caller's host twin then
# validates and classifies it.


def _ceil_log2(n):
    return max(1, int(n - 1).bit_length())


def _snappy_page(comp, comp_len, want_len, out_cap):
    """One snappy page: ``comp`` (src_cap,) uint8 → ((out_cap,) uint8, ok)."""
    import jax.numpy as jnp
    from jax import lax

    src_cap = comp.shape[0]
    b = jnp.pad(comp.astype(jnp.int32), (0, 5))
    b0, b1, b2, b3, b4 = (b[k:k + src_cap] for k in range(5))

    # preamble: varint uncompressed length (<= 5 bytes)
    out_len = jnp.int32(0)
    pos0 = jnp.int32(0)
    done = jnp.asarray(False)
    ok = jnp.asarray(True)
    for k in range(5):
        byte = b[k]
        live = ~done
        out_len = jnp.where(live, out_len | ((byte & 0x7F) << (7 * k)), out_len)
        pos0 = jnp.where(live, k + 1, pos0)
        ok = ok & (done | (k < comp_len))
        done = done | ((byte & 0x80) == 0)
    ok = ok & done & (out_len == want_len) & (out_len <= out_cap)

    # pass 1: a token parsed at every position
    i = jnp.arange(src_cap, dtype=jnp.int32)
    t = b0 & 3
    n0 = b0 >> 2
    is_lit = t == 0
    extra = jnp.where(n0 >= 60, n0 - 59, 0)  # 0..4 literal length bytes
    # shifts stay <= 24 (no dynamic 1 << 32); the 4-byte case keeps 31 bits —
    # a >=2GB literal in a page-sized stream is corruption the bounds reject
    mask = jnp.where(extra >= 4, jnp.int32(0x7FFFFFFF),
                     (jnp.int32(1) << (8 * jnp.minimum(extra, 3))) - 1)
    word = b1 | (b2 << 8) | (b3 << 16) | ((b4 & 0x7F) << 24)
    lit_len = jnp.where(n0 >= 60, word & mask, n0) + 1
    lit_start = i + 1 + extra
    cp_len = jnp.where(t == 1, (n0 & 0x7) + 4, n0 + 1)
    off = jnp.where(t == 1, ((b0 >> 5) << 8) | b1,
                    jnp.where(t == 2, b1 | (b2 << 8),
                              b1 | (b2 << 8) | (b3 << 16) | (b4 << 24)))
    nxt = jnp.where(is_lit, lit_start + lit_len,
                    i + jnp.where(t == 1, 2, jnp.where(t == 2, 3, 5)))
    olen = jnp.where(is_lit, lit_len, cp_len)
    # lit_len <= comp_len first: it keeps ``lit_start + lit_len`` from wrapping
    tok_ok = jnp.where(is_lit, (lit_len >= 1) & (lit_len <= comp_len),
                       off > 0) & (nxt <= comp_len)

    # pass 2: the token chain from pos0. Positions >= comp_len are fixed
    # points; a well-formed chain lands exactly on comp_len.
    idx = jnp.arange(src_cap + 1, dtype=jnp.int32)
    step = jnp.where(idx[:-1] < comp_len, jnp.clip(nxt, idx[:-1] + 1, src_cap),
                     idx[:-1])
    step = jnp.concatenate([step, jnp.full((1,), src_cap, jnp.int32)])

    def chain_round(_, state):
        reach, step = state
        return reach.at[step].max(reach), step[step]

    reach, _ = lax.fori_loop(0, _ceil_log2(src_cap + 1), chain_round,
                             ((idx == pos0).astype(jnp.int32), step))
    ok = ok & (reach[jnp.clip(comp_len, 0, src_cap)] > 0)
    tok = (reach[:-1] > 0) & (i < comp_len)
    tlen = jnp.where(tok, olen, 0)
    ends = jnp.cumsum(tlen)
    dst = ends - tlen
    ok = ok & (ends[-1] == out_len)
    ok = ok & jnp.all(~tok | (tok_ok & (is_lit | (off <= dst))))

    # pass 3: each output byte's owning token; copies chase back to literals
    j = jnp.arange(out_cap, dtype=jnp.int32)
    owner = jnp.clip(jnp.searchsorted(ends, j, side="right"), 0, src_cap - 1)
    rel = j - dst[owner]
    lit_byte = comp[jnp.clip(lit_start[owner] + rel, 0, src_cap - 1)]
    ptr = jnp.where(is_lit[owner], j, jnp.clip(j - off[owner], 0, out_cap - 1))
    ptr = lax.fori_loop(0, _ceil_log2(out_cap), lambda _, p: p[p], ptr)
    out = jnp.where(j < out_len, lit_byte[ptr], jnp.uint8(0))
    return out, ok


@functools.lru_cache(maxsize=64)
def _snappy_pages_fn(n_pages, src_cap, out_cap):
    """The jitted batch inflate for one (padded) page-table shape."""
    import jax

    def ptpu_pagedec_snappy(comp, meta):  # its name in a profile
        return jax.vmap(lambda c, m: _snappy_page(c, m[0], m[1], out_cap))(
            comp, meta)

    return jax.jit(ptpu_pagedec_snappy)


def snappy_inflate_pages(comp, meta, out_cap):
    """Inflate a batch of snappy pages on device.

    ``comp``: (n_pages, src_cap) uint8, zero-padded compressed pages.
    ``meta``: (n_pages, 2) int32 — [compressed_len, uncompressed_len] rows.
    Returns ``(raw (n_pages, out_cap) uint8, ok (n_pages,) bool)``.
    """
    import jax.numpy as jnp

    n, src_cap = comp.shape
    fn = _snappy_pages_fn(n, src_cap, int(out_cap))
    return fn(jnp.asarray(comp), jnp.asarray(meta, jnp.int32))


def stored_pages(comp, meta, out_cap):
    """The UNCOMPRESSED-codec twin of :func:`snappy_inflate_pages`: pages are
    already raw — pad/truncate to the output layout (pure device reshuffle,
    no kernel needed)."""
    import jax.numpy as jnp

    comp = jnp.asarray(comp)
    n, src_cap = comp.shape
    meta = jnp.asarray(meta, jnp.int32)
    if src_cap < out_cap:
        comp = jnp.pad(comp, ((0, 0), (0, out_cap - src_cap)))
    else:
        comp = comp[:, :out_cap]
    idx = jnp.arange(out_cap)[None, :]
    out = jnp.where(idx < meta[:, 1:2], comp, 0).astype(jnp.uint8)
    ok = meta[:, 0] == meta[:, 1]
    return out, ok


# -- RLE/bit-packed hybrid expansion ---------------------------------------------------

_MAX_BIT_WIDTH = 24  # 4-byte windows cover shift(<=7)+bw bits; wider -> host path


def _rle_run_scan(data, data_len, total, max_runs, bit_width):
    """Phase 1 (sequential, runs ≪ values): parse the hybrid run stream into
    a bounded run table. Returns (run_end, is_packed, rle_value,
    packed_bit_base, n_runs, ok)."""
    import jax.numpy as jnp
    from jax import lax

    cap = data.shape[0]
    data = data.astype(jnp.int32)

    def rd(i):
        return data[jnp.clip(i, 0, cap - 1)]

    byte_width = (bit_width + 7) // 8  # static

    def varint(pos, ok):
        def body(state):
            p, shift, val, done, ok = state
            b = rd(p)
            val = val | ((b & 0x7F) << shift)
            return p + 1, shift + 7, val, (b & 0x80) == 0, \
                ok & (p < data_len) & (shift <= 28)

        p, _, val, _, ok = lax.while_loop(
            lambda s: (~s[3]) & s[4], body,
            (pos, jnp.int32(0), jnp.int32(0), False, ok))
        return val, p, ok

    run_end = jnp.full((max_runs,), jnp.iinfo(jnp.int32).max, jnp.int32)
    is_packed = jnp.zeros((max_runs,), jnp.int32)
    rle_value = jnp.zeros((max_runs,), jnp.int32)
    bit_base = jnp.zeros((max_runs,), jnp.int32)

    def body(state):
        pos, filled, nruns, run_end, is_packed, rle_value, bit_base, ok = state
        header, pos, ok = varint(pos, ok)
        packed = (header & 1) == 1

        groups = header >> 1
        packed_n = groups * 8
        packed_bytes = groups * bit_width  # bytes per 8 values == bit_width
        rle_run = header >> 1
        v = jnp.int32(0)
        for k in range(byte_width):
            v = v | (rd(pos + k) << (8 * k))
        count = jnp.where(packed, packed_n, rle_run)
        consumed = jnp.where(packed, packed_bytes, byte_width)
        ok = ok & (pos + consumed <= data_len) & (count > 0) \
            & (nruns < max_runs)
        idx = jnp.clip(nruns, 0, max_runs - 1)
        # a packed run's trailing values beyond `total` are spec-legal padding
        run_end = run_end.at[idx].set(jnp.minimum(filled + count, total))
        is_packed = is_packed.at[idx].set(packed.astype(jnp.int32))
        rle_value = rle_value.at[idx].set(jnp.where(packed, 0, v))
        bit_base = bit_base.at[idx].set(pos * 8)
        return (pos + consumed, filled + count, nruns + 1,
                run_end, is_packed, rle_value, bit_base, ok)

    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0),
            run_end, is_packed, rle_value, bit_base, True)
    pos, filled, nruns, run_end, is_packed, rle_value, bit_base, ok = \
        lax.while_loop(lambda s: (s[1] < total) & s[7], body, init)
    ok = ok & (filled >= total)
    return run_end, is_packed, rle_value, bit_base, nruns, ok


def _rle_values(data, data_len, bit_width, total):
    """Traced two-phase expansion of one stream: (total,) int32 values, ok."""
    import jax.numpy as jnp

    cap = data.shape[0]
    max_runs = max(8, total)  # worst case: 1-value RLE runs
    run_end, is_packed, rle_value, bit_base, _nruns, ok = _rle_run_scan(
        data, data_len, total, max_runs, bit_width)
    i = jnp.arange(total, dtype=jnp.int32)
    rid = jnp.searchsorted(run_end, i, side="right").astype(jnp.int32)
    rid = jnp.clip(rid, 0, max_runs - 1)
    run_start = jnp.where(rid == 0, 0, run_end[jnp.clip(rid - 1, 0, max_runs - 1)])
    bitpos = bit_base[rid] + (i - run_start) * bit_width
    shift = (bitpos & 7).astype(jnp.int32)
    gather = jnp.clip((bitpos >> 3)[:, None] + jnp.arange(4)[None, :], 0, cap - 1)
    w = data[gather].astype(jnp.int32)
    word = w[:, 0] | (w[:, 1] << 8) | (w[:, 2] << 16) | (w[:, 3] << 24)
    packed = (word >> shift) & ((1 << bit_width) - 1)
    values = jnp.where(is_packed[rid] != 0, packed, rle_value[rid])
    return values, ok


def bitcast_values(raw_bytes, dtype):
    """(n*itemsize,) uint8 → (n,) ``dtype`` on device (little-endian, which
    both the CPU and TPU hosts are).

    On x64-disabled runtimes jax canonicalizes 8-byte dtypes: the classic
    path's ``device_put(np.int64 column)`` delivers int32 by value-truncation,
    which for little-endian two's complement IS the low word — so INT64
    bitcasts to (n, 2) int32 word pairs and keeps the low word, byte-identical
    to the classic delivery. FLOAT64 would need a value-rounding conversion a
    bitcast cannot express — it raises :class:`DeviceInflateError` and the
    column takes the host-reference fallback (still compressed on the wire,
    host-decoded before the transfer)."""
    import jax
    import jax.numpy as jnp

    dtype = np.dtype(dtype)
    k = dtype.itemsize
    n = raw_bytes.shape[0] // k
    words = raw_bytes[:n * k].reshape(n, k)
    x64 = bool(jax.config.jax_enable_x64)
    if k == 8 and not x64:
        if dtype.kind == "f":
            raise DeviceInflateError(
                "float64 device inflate needs jax_enable_x64 (host fallback)")
        pairs = jax.lax.bitcast_convert_type(
            words.reshape(n, 2, 4), jnp.int32)
        out = pairs[:, 0]
        return out.astype(jnp.uint32) if dtype.kind == "u" else out
    return jax.lax.bitcast_convert_type(words, jnp.dtype(dtype.name))


# -- loader-facing orchestration -------------------------------------------------------

def _bucket(n):
    """Padded size for a data-dependent extent: the next power of two (at
    least 128), so the jitted programs compile once per size class instead
    of once per page size."""
    return max(128, 1 << int(n - 1).bit_length())


def _pack_pages(chunk, pages):
    """Host prep of the device transfer: pad the COMPRESSED page payloads into
    one (n, src_cap) matrix + the (n, 2) page table. These bytes (plus the
    table) are exactly the H2D traffic the pass-through ships. Page count and
    both widths are bucketed (:func:`_bucket`); padding pages are all-zero
    rows whose ``ok`` the caller ignores."""
    src_cap = _bucket(max(p.comp_size for p in pages))
    out_cap = _bucket(max(p.uncomp_size for p in pages))
    n = 1 << int(len(pages) - 1).bit_length()
    comp = np.zeros((n, src_cap), np.uint8)
    meta = np.zeros((n, 2), np.int32)
    for i, p in enumerate(pages):
        payload = np.frombuffer(chunk.buf, np.uint8, count=p.comp_size,
                                offset=p.payload_offset)
        comp[i, :p.comp_size] = payload
        meta[i] = (p.comp_size, p.uncomp_size)
    return comp, meta, out_cap


def _inflate_chunk_pages(chunk, pages):
    """All of ``pages`` inflated on device: returns (raw (n, out_cap) uint8,
    per-page ok)."""
    comp, meta, out_cap = _pack_pages(chunk, pages)
    if chunk.codec == "SNAPPY":
        raw, ok = snappy_inflate_pages(comp, meta, out_cap)
    else:
        raw, ok = stored_pages(comp, meta, out_cap)
    return raw, ok[:len(pages)]


class DeviceInflateError(Exception):
    """Internal: the device path bailed (ok flag latched false / unsupported
    width) — the caller falls back to the host reference, which validates
    fully and raises the classified error if the bytes are actually bad."""


@functools.lru_cache(maxsize=256)
def _data_page_fn(out_cap, dtype_str, num_values, bit_width):
    """One jitted program per data-page shape class: the page's values from the
    inflated page matrix. ``bit_width=None`` is a PLAIN page; otherwise an
    RLE-dictionary page whose dictionary is row 0. Offsets and lengths are
    traced, so pages of one size class share the program.

    ``ptpu_pagedec_data_page(raw, row, off, data_len, dict_n)`` →
    ``(values (num_values,), ok)``:
    ``off`` is where the values section starts in the page (after the level
    block; for dictionary pages, after the bit-width byte too)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dtype = np.dtype(dtype_str)
    need = num_values * dtype.itemsize

    def ptpu_pagedec_data_page(raw, row, off, data_len, dict_n):
        body = jnp.pad(raw[row], (0, out_cap))  # a slice at off never clamps
        if bit_width is None:
            return bitcast_values(lax.dynamic_slice(body, (off,), (need,)),
                                  dtype), jnp.asarray(True)
        if bit_width == 0:  # a one-entry dictionary: every index is 0
            idx, ok = jnp.zeros((num_values,), jnp.int32), jnp.asarray(True)
        else:
            data = lax.dynamic_slice(body, (off,), (out_cap,))
            idx, ok = _rle_values(data, data_len, bit_width, num_values)
        ok = ok & jnp.all((idx >= 0) & (idx < dict_n))
        dict_vals = bitcast_values(raw[0], dtype)
        return dict_vals[jnp.clip(idx, 0, dict_vals.shape[0] - 1)], ok

    return jax.jit(ptpu_pagedec_data_page)


def inflate_window(chunk, skip, take):
    """Rows ``[skip, skip+take)`` of one
    :class:`~petastorm_tpu.io.pagedec.PassthroughChunk`, inflated on device
    from the COVERING pages only (plus the dictionary page when one exists)
    — cutting a row group into many batches ships and decodes each data
    page at most twice (boundary pages), never the whole chunk per batch.
    Raises :class:`DeviceInflateError` when any page's ok flag latches
    false."""
    import jax.numpy as jnp

    from petastorm_tpu.io import pagedec as _pd

    dtype = chunk.dtype
    p0, p1, base = chunk.covering_pages(skip, take)
    data_pages = list(chunk.pages[p0:p1])
    dict_page = chunk.dict_page
    pages = ([dict_page] if dict_page is not None else []) + data_pages
    raw, ok = _inflate_chunk_pages(chunk, pages)
    out_cap = raw.shape[1]
    dict_n = 0
    if dict_page is not None:
        if dict_page.uncomp_size < dict_page.num_values * dtype.itemsize:
            raise DeviceInflateError("dictionary page shorter than its values")
        dict_n = dict_page.num_values
    # what the host must know to lay out each page: the level-block length
    # and the dictionary bit width sit in the first bytes of the inflated
    # page — one small D2H for all pages of the window
    head = np.asarray(raw[len(pages) - len(data_pages):len(pages), :64])
    oks = [jnp.all(ok)]
    outs = []
    for i, page in enumerate(data_pages):
        row = len(pages) - len(data_pages) + i
        off = 0
        if chunk.max_def:
            if page.uncomp_size < 4:
                raise DeviceInflateError("page too short for level block")
            off = 4 + int(head[i, :4].view("<u4")[0])
            if off > page.uncomp_size:
                raise DeviceInflateError("level block past page end")
        if page.encoding == _pd.ENC_PLAIN:
            if page.uncomp_size - off < page.num_values * dtype.itemsize:
                raise DeviceInflateError("PLAIN page shorter than its values")
            bit_width = None
            data_len = 0
        else:  # RLE_DICTIONARY / PLAIN_DICTIONARY
            if dict_page is None:
                raise DeviceInflateError("dictionary page missing")
            if page.uncomp_size - off < 1:
                raise DeviceInflateError("empty dictionary-index body")
            bw_byte = head[i, off] if off < head.shape[1] \
                else np.asarray(raw[row, off])
            bit_width = int(bw_byte)
            if bit_width > _MAX_BIT_WIDTH:
                raise DeviceInflateError("dictionary bit width %d" % bit_width)
            off += 1
            data_len = page.uncomp_size - off
        fn = _data_page_fn(out_cap, dtype.str, page.num_values, bit_width)
        values, page_ok = fn(raw, row, off, data_len, dict_n)
        outs.append(values)
        oks.append(page_ok)
    if not bool(jnp.all(jnp.stack(oks))):
        raise DeviceInflateError("page inflate latched ok=False")
    full = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    if skip == base and take == full.shape[0]:
        return full
    return full[skip - base:skip - base + take]


def inflate_chunk(chunk):
    """All rows of one chunk (the full-range window) — test/CLI convenience."""
    return inflate_window(chunk, 0, chunk.num_rows)


def inflate_column(column):
    """The loader's device inflate: a
    :class:`~petastorm_tpu.io.pagedec.PassthroughColumn` window → the decoded
    device array for exactly its rows, one covering-pages inflate per
    window. Raises :class:`DeviceInflateError` for the caller's host
    fallback."""
    import jax.numpy as jnp

    outs = []
    for chunk, skip, take in column.parts:
        if take == 0:
            continue
        outs.append(inflate_window(chunk, skip, take))
    if not outs:
        return jnp.zeros((0,), jnp.dtype(column.dtype.name))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)
