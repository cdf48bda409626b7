"""Two-stage JPEG decode: host entropy decode → device dequant+IDCT+color (SURVEY.md §8,
hard part #1).

Huffman entropy decoding is sequential and branchy — a poor fit for TPU vector units — but
it is only ~10% of JPEG decode FLOPs. The split:

- **Stage 1 (host)**: :func:`entropy_decode_jpeg` parses a baseline JPEG and Huffman-decodes
  the scan into *quantized DCT coefficient blocks* per component (pure python/numpy here; a
  native decoder can swap in behind the same output contract).
- **Stage 2 (device)**: :func:`decode_jpeg_device_stage` runs dequantization, 8×8 inverse
  DCT (one (N,64)@(64,64) matmul per plane — MXU work), level shift, chroma upsampling and
  YCbCr→RGB as one jitted program; the IDCT matmul is a Pallas kernel on TPU.

The classic full-host path stays available via ``CompressedImageCodec`` (cv2), which is also
the correctness oracle for the tests.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import os
import struct
import threading

import numpy as np

from petastorm_tpu.trace import tally

# -- zigzag order (JPEG spec, Figure A.6) ----------------------------------------------

ZIGZAG = np.array([
    0, 1, 8, 16, 9, 2, 3, 10, 17, 24, 32, 25, 18, 11, 4, 5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6, 7, 14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63,
], dtype=np.int32)
UNZIGZAG = np.argsort(ZIGZAG)


@dataclasses.dataclass
class JpegComponent:
    blocks: np.ndarray      # (blocks_y, blocks_x, 64) int32, natural (unzigzagged) order
    qtable: np.ndarray      # (64,) int32, natural order
    h_samp: int
    v_samp: int


@dataclasses.dataclass
class JpegPlanes:
    height: int
    width: int
    components: list        # [Y, Cb, Cr] or [Y]
    #: set by the row-group batched stage 1: ``(coeffs_tuple, qtabs_array, row_index)``
    #: where each component's ``blocks`` is a zero-copy view into ``coeffs_tuple[c]``.
    #: Lets :func:`_pack_group` read a batch's rows straight from the parent buffers,
    #: and :func:`stack_jpeg_coefficients` slice or gather them, instead of np.stack
    #: over per-row objects.
    batch_ref: tuple | None = None
    #: per component, the max ZIGZAG index with any nonzero coefficient (from the
    #: native batch decode) — lets the device transfer ship only the zigzag prefix.
    kmax: tuple | None = None
    #: ``(ncomp, 64)`` int32 per-zigzag-position max |coefficient| over the row group
    #: (shared array across the group's rows) — drives the per-position bit-width
    #: transfer split. None when stage 1 did not profile the spectrum.
    specmax: object = None

    def detach(self):
        """Return an equivalent ``JpegPlanes`` that owns its own coefficient copies.

        A ``batch_ref`` row pins its ENTIRE row group's stacked buffers alive (its
        blocks are views); long-lived rows — e.g. stragglers in a shuffling buffer that
        interleaves many row groups — must be detached so host memory scales with rows
        in flight, not row groups touched. ``specmax`` stays shared (tiny, immutable)."""
        if self.batch_ref is None:
            return self
        comps = [
            JpegComponent(c.blocks.copy(), c.qtable.copy(), c.h_samp, c.v_samp)
            for c in self.components
        ]
        return JpegPlanes(self.height, self.width, comps, batch_ref=None,
                          kmax=self.kmax, specmax=self.specmax)

    def __reduce__(self):
        # pickle (process-pool IPC, disk cache) must ship ONLY this row: the default
        # reduce would serialize batch_ref's entire row-group buffers per row
        d = self.detach()
        return (JpegPlanes, (d.height, d.width, d.components, None, d.kmax, d.specmax))


class _HuffTable:
    __slots__ = ("lookup", "max_len")

    def __init__(self, counts, symbols):
        self.lookup = {}
        code = 0
        k = 0
        self.max_len = 0
        for length in range(1, 17):
            for _ in range(counts[length - 1]):
                self.lookup[(length, code)] = symbols[k]
                self.max_len = length
                code += 1
                k += 1
            code <<= 1


class _BitReader:
    """MSB-first bit reader over an entropy-coded segment with 0xFF00 byte-stuffing."""

    __slots__ = ("data", "pos", "bitbuf", "bitcnt")

    def __init__(self, data, pos):
        self.data = data
        self.pos = pos
        self.bitbuf = 0
        self.bitcnt = 0

    def _fill(self):
        while self.bitcnt <= 24:
            if self.pos >= len(self.data):
                b = 0  # pad with zeros past the end (spec allows)
            else:
                b = self.data[self.pos]
                if b == 0xFF:
                    nxt = self.data[self.pos + 1] if self.pos + 1 < len(self.data) else 0xD9
                    if nxt == 0x00:
                        self.pos += 2  # byte-stuffed 0xFF data byte
                    else:
                        # restart or real marker: stop feeding real bytes, pad zeros
                        # (align_restart advances past RSTn when the caller asks)
                        b = 0
                else:
                    self.pos += 1
            self.bitbuf = (self.bitbuf << 8) | b
            self.bitcnt += 8

    def read_bit(self):
        if self.bitcnt == 0:
            self._fill()
        self.bitcnt -= 1
        return (self.bitbuf >> self.bitcnt) & 1

    def read_bits(self, n):
        v = 0
        for _ in range(n):
            v = (v << 1) | self.read_bit()
        return v

    def align_restart(self):
        """Skip to just past the next RSTn marker; reset bit state."""
        self.bitbuf = 0
        self.bitcnt = 0
        d = self.data
        while self.pos + 1 < len(d):
            if d[self.pos] == 0xFF and 0xD0 <= d[self.pos + 1] <= 0xD7:
                self.pos += 2
                return
            self.pos += 1

    def decode_huff(self, table):
        length = 0
        code = 0
        while length < 16:
            code = (code << 1) | self.read_bit()
            length += 1
            sym = table.lookup.get((length, code))
            if sym is not None:
                return sym
        raise ValueError("Invalid Huffman code in JPEG stream")


def _extend(v, t):
    """JPEG EXTEND: map t-bit magnitude to signed value."""
    return v if v >= (1 << (t - 1)) else v - (1 << t) + 1


def entropy_decode_jpeg(data):
    """Baseline-JPEG stage 1: bytes → :class:`JpegPlanes` of quantized DCT blocks."""
    if data[:2] != b"\xff\xd8":
        raise ValueError("Not a JPEG (missing SOI)")
    pos = 2
    qtables = {}
    huff_dc, huff_ac = {}, {}
    frame = None
    restart_interval = 0
    while pos < len(data):
        if data[pos] != 0xFF:
            pos += 1
            continue
        marker = data[pos + 1]
        pos += 2
        if marker in (0xD8, 0x01) or 0xD0 <= marker <= 0xD7:
            continue
        if marker == 0xD9:  # EOI
            break
        (seglen,) = struct.unpack(">H", data[pos: pos + 2])
        seg = data[pos + 2: pos + seglen]
        if marker == 0xDB:  # DQT
            s = 0
            while s < len(seg):
                pq, tq = seg[s] >> 4, seg[s] & 0xF
                s += 1
                if pq:
                    q = np.frombuffer(seg[s: s + 128], dtype=">u2").astype(np.int32)
                    s += 128
                else:
                    q = np.frombuffer(seg[s: s + 64], dtype=np.uint8).astype(np.int32)
                    s += 64
                qtables[tq] = q  # kept in zigzag order; unzigzagged in _decode_scan
        elif marker == 0xC0 or marker == 0xC1:  # SOF0/1 baseline
            precision, h, w, nc = seg[0], struct.unpack(">H", seg[1:3])[0], \
                struct.unpack(">H", seg[3:5])[0], seg[5]
            if precision != 8:
                raise ValueError("Only 8-bit baseline JPEG supported")
            comps = []
            for i in range(nc):
                cid, samp, tq = seg[6 + 3 * i], seg[7 + 3 * i], seg[8 + 3 * i]
                comps.append({"id": cid, "h": samp >> 4, "v": samp & 0xF, "tq": tq})
            frame = {"h": h, "w": w, "comps": comps}
        elif marker in (0xC2, 0xC3, 0xC5, 0xC6, 0xC7, 0xC9, 0xCA, 0xCB, 0xCD, 0xCE, 0xCF):
            raise ValueError("Unsupported JPEG mode (progressive/lossless); marker %02x"
                             % marker)
        elif marker == 0xC4:  # DHT
            s = 0
            while s < len(seg):
                tc, th = seg[s] >> 4, seg[s] & 0xF
                counts = list(seg[s + 1: s + 17])
                total = sum(counts)
                symbols = list(seg[s + 17: s + 17 + total])
                table = _HuffTable(counts, symbols)
                (huff_dc if tc == 0 else huff_ac)[th] = table
                s += 17 + total
        elif marker == 0xDD:  # DRI
            restart_interval = struct.unpack(">H", seg[:2])[0]
        elif marker == 0xDA:  # SOS
            ns = seg[0]
            scan = []
            for i in range(ns):
                cs, tables = seg[1 + 2 * i], seg[2 + 2 * i]
                scan.append({"id": cs, "dc": tables >> 4, "ac": tables & 0xF})
            return _decode_scan(data, pos + seglen, frame, scan, qtables,
                                huff_dc, huff_ac, restart_interval)
        pos += seglen
    raise ValueError("No SOS marker found")


def _decode_scan(data, pos, frame, scan, qtables, huff_dc, huff_ac, restart_interval):
    h, w, comps = frame["h"], frame["w"], frame["comps"]
    hmax = max(c["h"] for c in comps)
    vmax = max(c["v"] for c in comps)
    mcus_x = -(-w // (8 * hmax))
    mcus_y = -(-h // (8 * vmax))
    out = []
    for c in comps:
        bx = mcus_x * c["h"]
        by = mcus_y * c["v"]
        out.append(np.zeros((by, bx, 64), np.int32))

    reader = _BitReader(data, pos)
    pred = [0] * len(comps)
    mcu_count = 0
    for my in range(mcus_y):
        for mx in range(mcus_x):
            if restart_interval and mcu_count and mcu_count % restart_interval == 0:
                reader.align_restart()
                pred = [0] * len(comps)
            for ci, c in enumerate(comps):
                sc = next(s for s in scan if s["id"] == c["id"])
                dc_t, ac_t = huff_dc[sc["dc"]], huff_ac[sc["ac"]]
                for v in range(c["v"]):
                    for hh in range(c["h"]):
                        block = np.zeros(64, np.int32)
                        t = reader.decode_huff(dc_t)
                        diff = _extend(reader.read_bits(t), t) if t else 0
                        pred[ci] += diff
                        block[0] = pred[ci]
                        k = 1
                        while k < 64:
                            rs = reader.decode_huff(ac_t)
                            r, s = rs >> 4, rs & 0xF
                            if s == 0:
                                if r == 15:
                                    k += 16
                                    continue
                                break  # EOB
                            k += r
                            if k > 63:
                                break
                            block[k] = _extend(reader.read_bits(s), s)
                            k += 1
                        out[ci][my * c["v"] + v, mx * c["h"] + hh] = block[UNZIGZAG]
            mcu_count += 1

    components = []
    for ci, c in enumerate(comps):
        q = qtables[c["tq"]][UNZIGZAG].astype(np.int32)
        components.append(JpegComponent(out[ci], q, c["h"], c["v"]))
    return JpegPlanes(height=h, width=w, components=components)


# -- stage 2: device ------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _idct_basis():
    """(64, 64) flattened 2-D IDCT basis: pixels_flat = coeffs_flat @ B."""
    a = np.zeros((8, 8), np.float64)
    for u in range(8):
        alpha = np.sqrt(0.25) if u else np.sqrt(0.125)
        for p in range(8):
            a[u, p] = alpha * np.cos((2 * p + 1) * u * np.pi / 16.0)
    return np.kron(a, a).astype(np.float32)  # rows (u,v) -> cols (p,q)


def _idct_kernel(coef_ref, basis_ref, out_ref):
    import jax.numpy as jnp

    out_ref[:] = jnp.dot(coef_ref[:], basis_ref[:],
                         preferred_element_type=jnp.float32) + 128.0


def idct_blocks(coeffs, qtable):
    """(N, 64) quantized coefficients → (N, 64) pixel blocks (dequant + IDCT + shift).

    Pallas matmul on TPU; interpret mode on CPU topologies.
    """
    import jax.numpy as jnp

    scaled = coeffs.astype(jnp.float32) * qtable.astype(jnp.float32)[None, :]
    return _idct_scaled(scaled)


def _blocks_to_plane(pixels, blocks_y, blocks_x):
    """(by*bx, 64) → (by*8, bx*8) spatial plane."""
    import jax.numpy as jnp

    p = pixels.reshape(blocks_y, blocks_x, 8, 8)
    return jnp.transpose(p, (0, 2, 1, 3)).reshape(blocks_y * 8, blocks_x * 8)


def _fancy_upsample2(plane, axis, limit=None):
    """libjpeg 'fancy' 2x upsampling along ``axis``: triangle filter (3*near + far) / 4,
    edges clamped — matches libjpeg/cv2 output much closer than pixel doubling.

    ``limit`` (per image of an ``(n, h, w)`` stack) clamps each image at its own
    last real sample, as libjpeg clamps at a component's downsampled size, where
    the stack's edge lies past it (a canvas)."""
    import jax.numpy as jnp

    plane = jnp.moveaxis(plane, axis, 0)
    prev = jnp.concatenate([plane[:1], plane[:-1]], axis=0)
    nxt = jnp.concatenate([plane[1:], plane[-1:]], axis=0)
    if limit is not None:
        idx = jnp.arange(plane.shape[0]).reshape(-1, 1, 1)
        nxt = jnp.where(idx >= jnp.reshape(limit, (1, -1, 1)) - 1, plane, nxt)
    even = (3.0 * plane + prev) * 0.25
    odd = (3.0 * plane + nxt) * 0.25
    out = jnp.stack([even, odd], axis=1).reshape((-1,) + plane.shape[1:])
    return jnp.moveaxis(out, 0, axis)


def ycbcr_to_rgb(y, cb, cr):
    """JFIF YCbCr → RGB (float in, float out, unclamped)."""
    import jax.numpy as jnp

    r = y + 1.402 * (cr - 128.0)
    g = y - 0.344136 * (cb - 128.0) - 0.714136 * (cr - 128.0)
    b = y + 1.772 * (cb - 128.0)
    return jnp.stack([r, g, b], axis=-1)


def decode_jpeg_device_stage(planes):
    """Stage 2: :class:`JpegPlanes` → (h, w, 3) uint8 RGB ``jax.Array`` (grayscale → 3ch)."""
    import jax.numpy as jnp

    outs = []
    for comp in planes.components:
        by, bx, _ = comp.blocks.shape
        pix = idct_blocks(jnp.asarray(comp.blocks.reshape(-1, 64)),
                          jnp.asarray(comp.qtable))
        # libjpeg range-limits every sample at IDCT output, before upsampling/color
        pix = jnp.clip(jnp.round(pix), 0.0, 255.0)
        outs.append(_blocks_to_plane(pix, by, bx))
    hmax = max(c.h_samp for c in planes.components)
    vmax = max(c.v_samp for c in planes.components)
    full = []
    for comp, plane in zip(planes.components, outs):
        ry, rx = vmax // comp.v_samp, hmax // comp.h_samp
        for axis, r in ((0, ry), (1, rx)):
            if r == 2:
                plane = _fancy_upsample2(plane, axis)  # libjpeg triangle filter
            elif r > 1:
                plane = jnp.repeat(plane, r, axis=axis)
        full.append(plane[: planes.height, : planes.width])
    if len(full) == 1:
        y = jnp.clip(full[0], 0, 255).astype(jnp.uint8)
        return jnp.stack([y, y, y], axis=-1)
    rgb = ycbcr_to_rgb(full[0], full[1], full[2])
    return jnp.clip(jnp.round(rgb), 0, 255).astype(jnp.uint8)


def decode_jpeg(data):
    """Full two-stage decode: JPEG bytes → (h, w, 3) uint8 RGB on device."""
    return decode_jpeg_device_stage(entropy_decode_jpeg(data))


# -- fast stage 1 (native C++ behind the same contract) --------------------------------


def entropy_decode_jpeg_fast(data):
    """Stage 1 via the compiled C++ decoder (petastorm_tpu/ops/native/jpeg_decoder.cpp);
    falls back to the pure-Python oracle when the native build is unavailable.

    This is the data-plane entry point: ctypes releases the GIL so reader thread pools
    run stage-1 decode truly in parallel. One decode call fills arrays of the image's
    own and gives the spectral profile and kmax a row-group decode would, so that a
    batch of per-image rows (mixed sizes) still ships narrowed. Raises ValueError on
    streams the two-stage path cannot handle (lossless/arithmetic, CMYK, corrupt) —
    the codec layer catches that and falls back to full host decode per stream."""
    from petastorm_tpu.ops import native

    if native.native_available():
        height, width, comps, kmax, specmax, symbols, fast = \
            native.jpeg_decode_image_native(data)
        tally(stage1_symbols=symbols, stage1_fast_symbols=fast)
        planes = JpegPlanes(height, width,
                            [JpegComponent(*c) for c in comps], kmax=kmax, specmax=specmax)
    else:
        planes = entropy_decode_jpeg(bytes(data))
    if len(planes.components) not in (1, 3):
        # stage 2 models grayscale and YCbCr only; 2-component or Adobe CMYK streams
        # must not reach the jitted decoder (wrong colors / shape errors inside jit)
        raise ValueError(
            "Unsupported JPEG component count %d (expected 1 or 3)"
            % len(planes.components)
        )
    return planes


def entropy_decode_jpeg_batch(blobs):
    """Row-group batched stage 1: list of JPEG byte strings → list of :class:`JpegPlanes`
    (or ``None`` per stream the batch decoder could not handle — caller re-decodes those
    individually).

    One native call decodes every same-layout stream straight into stacked coefficient
    buffers (no per-image ctypes overhead, no copies, GIL released throughout) and
    folds the row group's spectral range profile (shared by its rows; it drives the
    split-pack transfer) into the same pass; each returned ``JpegPlanes`` holds
    zero-copy views into those buffers plus a ``batch_ref`` so downstream batching can
    slice the parent arrays directly.

    Raises RuntimeError when the native decoder is unavailable and ValueError when the
    first stream has no usable baseline layout (callers fall back to the per-image path).
    """
    from petastorm_tpu.ops import native

    if not native.native_available():
        raise RuntimeError("native jpeg decoder unavailable: %s" % native.native_error())
    one = native.jpeg_decode_coeffs_batch_native(blobs)
    tally(stage1_symbols=one.symbols, stage1_fast_symbols=one.fast_symbols)
    height, width, comps_layout = one.layout
    if len(comps_layout) not in (1, 3):
        raise ValueError(
            "Unsupported JPEG component count %d (expected 1 or 3)" % len(comps_layout)
        )
    coeffs, qtabs = one.coeffs, one.qtabs
    out = []
    for i in range(len(blobs)):
        if one.status[i] != 0:
            out.append(None)
            continue
        comps = [
            JpegComponent(coeffs[c][i].reshape(by, bx, 64), qtabs[i, c], h, v)
            for c, (h, v, by, bx) in enumerate(comps_layout)
        ]
        out.append(JpegPlanes(height, width, comps, batch_ref=(coeffs, qtabs, i),
                              kmax=one.kmax, specmax=one.specmax))
    return out


def jpeg_size(blob):
    """``(height, width)`` from a JPEG's frame header, or None where no frame header
    comes before the first scan. A walk over the marker segments (whose lengths skip
    APPn payloads such as an EXIF thumbnail's own frame); no entropy decode."""
    n = len(blob)
    if n < 4 or blob[0] != 0xFF or blob[1] != 0xD8:
        return None
    i = 2
    while i + 9 <= n:
        if blob[i] != 0xFF:
            return None
        marker = blob[i + 1]
        if marker == 0xFF:  # fill byte
            i += 1
            continue
        if 0xC0 <= marker <= 0xCF and marker not in (0xC4, 0xC8, 0xCC):
            height, width = struct.unpack_from(">HH", blob, i + 5)
            return height, width
        if marker in (0xD9, 0xDA):
            return None
        i += 2 + struct.unpack_from(">H", blob, i + 2)[0]
    return None


class _StageJob:
    __slots__ = ("fn", "out", "left", "error", "done")

    def __init__(self, fn, n):
        self.fn = fn
        self.out = [None] * n
        self.left = n
        self.error = None
        self.done = threading.Event()


#: the per-image stage-1 tasks of every ``stage_images`` call in flight, oldest first
_STAGE_TASKS = collections.deque()
_STAGE_LOCK = threading.Lock()


def stage_images(fn, items):
    """``[fn(x) for x in items]``, the work shared among the threads that call this
    at the same time, oldest call first.

    A call queues one task per item at the tail of a process-wide queue, then runs
    tasks from its head, whichever call queued them, until its own are all done.
    The reader pool's workers stage row groups of mixed sizes image by image; this
    way the row group claimed first is staged first, by every worker that is free,
    and row groups come out one after another rather than in bursts of as many as
    there are workers (which a step's p95 reads as a stall). The threads and the
    work are the same; only the order differs. An exception raised by ``fn`` on this
    call's items is raised here once they are all done."""
    job = _StageJob(fn, len(items))
    if not items:
        return job.out
    with _STAGE_LOCK:
        _STAGE_TASKS.extend((job, i, x) for i, x in enumerate(items))
    while not job.done.is_set():
        with _STAGE_LOCK:
            task = _STAGE_TASKS.popleft() if _STAGE_TASKS else None
        if task is None:
            job.done.wait()  # the rest of ours are running on other threads
            break
        owner, i, x = task
        try:
            owner.out[i] = owner.fn(x)
        except Exception as e:  # noqa: BLE001 — re-raised on the owner's thread
            error = e
        else:
            error = None
        with _STAGE_LOCK:
            if owner.error is None:
                owner.error = error
            owner.left -= 1
            if owner.left == 0:
                owner.done.set()
    if job.error is not None:
        raise job.error
    return job.out


# -- batched stage 2 (one device dispatch per image batch) -----------------------------


def _layout_key(planes):
    """Hashable decode layout: everything that shapes the compiled program."""
    return (
        planes.height,
        planes.width,
        tuple(
            (c.h_samp, c.v_samp, c.blocks.shape[0], c.blocks.shape[1])
            for c in planes.components
        ),
    )


def _idct_scaled(scaled):
    """(N, 64) dequantized float32 coefficients → (N, 64) pixel blocks (+128 level shift)."""
    import jax
    from jax.experimental import pallas as pl
    import jax.numpy as jnp

    n = scaled.shape[0]
    basis = jnp.asarray(_idct_basis())
    block_n = 512
    padded_n = ((n + block_n - 1) // block_n) * block_n
    if padded_n != n:
        scaled = jnp.pad(scaled, ((0, padded_n - n), (0, 0)))
    out = pl.pallas_call(
        _idct_kernel,
        out_shape=jax.ShapeDtypeStruct((padded_n, 64), jnp.float32),
        grid=(padded_n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 64), lambda i: (i, 0)),
            pl.BlockSpec((64, 64), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 64), lambda i: (i, 0)),
        interpret=jax.default_backend() == "cpu",
    )(scaled, basis)
    return out[:n]


def _unpack12(u8):
    """(n, blocks, m*3) uint8 → (n, blocks, 2m) int32, 12-bit two's complement."""
    import jax.numpy as jnp

    triples = u8.reshape(u8.shape[0], u8.shape[1], -1, 3)
    b0 = triples[..., 0].astype(jnp.int32)
    b1 = triples[..., 1].astype(jnp.int32)
    b2 = triples[..., 2].astype(jnp.int32)
    lo = b0 | ((b1 & 0xF) << 8)
    hi = (b1 >> 4) | (b2 << 4)
    pair = jnp.stack([lo, hi], axis=-1)
    pair = pair - ((pair & 0x800) << 1)  # sign-extend 12-bit
    return pair.reshape(u8.shape[0], u8.shape[1], -1)


def _unpack4(u8):
    """(n, blocks, m) uint8 → (n, blocks, 2m) int32, 4-bit two's complement."""
    import jax.numpy as jnp

    b = u8.astype(jnp.int32)
    lo = b & 0xF
    hi = (b >> 4) & 0xF
    pair = jnp.stack([lo, hi], axis=-1)
    pair = pair - ((pair & 0x8) << 1)  # sign-extend 4-bit
    return pair.reshape(u8.shape[0], u8.shape[1], -1)


def _unpack_coefficients(coef, k_ship, split, packed):
    """One component's shipped coefficients → ``(n, blocks, 64)`` natural order:
    the split-pack slab tuple (``split`` its ``(k1, k2)``), a 12-bit pack
    (``packed``) or int16, of the zigzag prefix ``k_ship`` when below 64."""
    import jax.numpy as jnp

    # HOST constant, deliberately: a device array closed over by a jitted
    # program would be lowered via a D2H fetch at every new variant's compile
    # (measured MINUTES when that fetch queues behind in-flight transfers)
    unzig = np.asarray(UNZIGZAG)
    if split is not None:
        head, mid, tail = coef
        parts = []
        if head.shape[-1]:
            parts.append(_unpack12(head))
        if mid.shape[-1]:
            parts.append(mid.astype(jnp.int32))
        if tail.shape[-1]:
            parts.append(_unpack4(tail))
        coef = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
        if k_ship < 64:
            coef = jnp.pad(coef, ((0, 0), (0, 0), (0, 64 - k_ship)))
        return jnp.take(coef, unzig, axis=-1)
    if packed:
        coef = _unpack12(coef)
    if k_ship < 64:
        coef = jnp.pad(coef, ((0, 0), (0, 0), (0, 64 - k_ship)))
        coef = jnp.take(coef, unzig, axis=-1)
    return coef


@functools.lru_cache(maxsize=32)
def _batched_stage2(layout, ks=None, packed=None, split=None, spmd=None):
    """Layout-specialized jitted decoder: stacked coefficient arrays → (n, h, w, 3)
    uint8 RGB. One Pallas IDCT dispatch per component for the WHOLE batch (vs one jit
    per image — VERDICT r1 #1). The batch size is taken from the input shapes, so jit's
    own shape specialization handles varying group sizes.

    ``ks`` (per component, multiples of 8) selects the zigzag-truncated transfer
    variant: inputs arrive as ``(n, blocks, k)`` zigzag-prefix packs (all dropped
    coefficients are zero — ``kmax`` contract) and are zero-padded + inverse-permuted
    back to natural order on device, fused into the same program. Bit-identical
    output; ~k/64 of the H2D bytes.

    ``packed`` (per component, bool) selects the 12-bit transfer variant on top:
    inputs arrive as ``(n, blocks, k*3//2)`` uint8 (two coefficients per 3 bytes,
    ``ptpu_jpeg_pack12`` layout) and are unpacked to int16 with fused integer ops
    before the pad/unpermute. Exact for |coeff| ≤ 2047 (the native packer verifies
    and falls back to int16 otherwise) — so output stays bit-identical at 75% of
    even the truncated H2D bytes.

    ``split`` (per component, None or ``(k1, k2)``) selects the spectral bit-width
    split instead: the component arrives as a tuple of slabs — 12-bit pairs for
    zigzag positions [0, k1), int8 for [k1, k2), 4-bit nibble pairs for [k2, k) —
    chosen from the row group's measured per-position ranges (``specmax``). Unpack is
    fused integer ops; order is always zigzag, so the pad/unpermute applies even at
    k = 64. Bit-identical output; sharp photographic content that defeats zigzag
    truncation still drops to ~half the 12-bit bytes (high positions are heavily
    quantized). A split entry overrides ``packed`` for that component.

    ``spmd`` (a batch-axis ``NamedSharding``, from :func:`_stage_inputs`) runs
    the program under ``shard_map``, one batch shard per device: every op is
    per image, and a Pallas kernel cannot be partitioned automatically."""
    import jax
    import jax.numpy as jnp

    height, width, comp_layout = layout
    hmax = max(h for h, _v, _by, _bx in comp_layout)
    vmax = max(v for _h, v, _by, _bx in comp_layout)

    # the program's name in a profile (``jit_ptpu_jpeg_stage2``), and its
    # phases as named scopes: unpack, dequant_idct, upsample, color
    def ptpu_jpeg_stage2(coeffs, qtabs):
        n = (coeffs[0][0] if isinstance(coeffs[0], tuple) else coeffs[0]).shape[0]
        planes = []
        for ci, ((h_samp, v_samp, by, bx), coef, qtab) in enumerate(
                zip(comp_layout, coeffs, qtabs)):
            # coef: (n, by*bx, 64) int16 natural order — or (n, by*bx, ks[ci])
            # zigzag prefix when this component was truncated, or the 12-bit uint8
            # pack of either, or the split-pack slab tuple; qtab: (n, 64) int32
            # (per-image: quality may vary)
            with jax.named_scope("unpack"):
                coef = _unpack_coefficients(
                    coef, ks[ci] if ks is not None else 64,
                    split[ci] if split is not None else None,
                    packed is not None and packed[ci])
            with jax.named_scope("dequant_idct"):
                scaled = coef.astype(jnp.float32) * qtab.astype(jnp.float32)[:, None, :]
                pix = _idct_scaled(scaled.reshape(n * by * bx, 64))
                # libjpeg range-limits at the IDCT's output
                pix = jnp.clip(jnp.round(pix), 0.0, 255.0)
            with jax.named_scope("upsample"):
                plane = pix.reshape(n, by, bx, 8, 8)
                plane = jnp.transpose(plane, (0, 1, 3, 2, 4)).reshape(n, by * 8, bx * 8)
                ry, rx = vmax // v_samp, hmax // h_samp
                for axis, r in ((1, ry), (2, rx)):
                    if r == 2:
                        plane = _fancy_upsample2(plane, axis)
                    elif r > 1:
                        plane = jnp.repeat(plane, r, axis=axis)
                planes.append(plane[:, :height, :width])
        with jax.named_scope("color"):
            if len(planes) == 1:
                y = jnp.clip(planes[0], 0, 255).astype(jnp.uint8)
                return jnp.stack([y, y, y], axis=-1)
            rgb = ycbcr_to_rgb(planes[0], planes[1], planes[2])
            return jnp.clip(jnp.round(rgb), 0, 255).astype(jnp.uint8)

    # NOTE on donation (VERDICT r3 #4 asked to try it): the slab buffers cannot
    # alias into the (n, h, w, 3) uint8 output — XLA input-output aliasing needs
    # size-compatible pairs — so donate_argnums only produces "donated buffers were
    # not usable" warnings on TPU (measured; no perf or memory change). The real
    # dispatch win is the explicit async device_put in ``_stage_inputs``.
    fn = ptpu_jpeg_stage2
    if spmd is not None:
        batch = jax.sharding.PartitionSpec(spmd.spec[0])
        # check_vma off: the Pallas IDCT's output declares no varying axes,
        # and a per-image program has no collective for the check to guard
        fn = jax.shard_map(fn, mesh=spmd.mesh, in_specs=(batch, batch),
                           out_specs=batch, check_vma=False)
    return jax.jit(fn)


def stack_jpeg_coefficients(planes_list):
    """Stack same-layout :class:`JpegPlanes` into per-component batch arrays.

    Returns ``(coeffs, qtabs)``: tuples with one ``(n, by*bx, 64)`` int16 and one
    ``(n, 64)`` int array per component — the host-side staging format the batched
    device stage consumes.

    Fast path: rows produced by :func:`entropy_decode_jpeg_batch` carry a ``batch_ref``
    into their row group's stacked buffers; when every row shares one parent, batches
    are a slice (consecutive rows — zero copy) or one fancy-index gather of the parent
    instead of an np.stack over hundreds of per-row objects."""
    ref = planes_list[0].batch_ref
    if ref is not None:
        parent_coeffs, parent_qtabs, _ = ref
        idx = np.empty(len(planes_list), dtype=np.intp)
        ok = True
        for j, p in enumerate(planes_list):
            r = p.batch_ref
            if r is None or r[0] is not parent_coeffs:
                ok = False
                break
            idx[j] = r[2]
        if ok:
            n = len(idx)
            first = int(idx[0])
            consecutive = int(idx[-1]) == first + n - 1 and \
                np.array_equal(idx, np.arange(first, first + n))
            coeffs = []
            qtabs = []
            for c in range(len(planes_list[0].components)):
                parent = parent_coeffs[c]
                qt = parent_qtabs[:, c, :]
                if consecutive:
                    coeffs.append(parent[first:first + n])
                    qtabs.append(qt[first:first + n])
                else:
                    coeffs.append(parent[idx])
                    qtabs.append(qt[idx])
            return tuple(coeffs), tuple(qtabs)
    ncomp = len(planes_list[0].components)
    coeffs = []
    qtabs = []
    for c in range(ncomp):
        coeffs.append(np.stack(
            [p.components[c].blocks.reshape(-1, 64) for p in planes_list]
        ))
        qtabs.append(np.stack([p.components[c].qtable for p in planes_list]))
    return tuple(coeffs), tuple(qtabs)


@functools.lru_cache(maxsize=None)
def _to_bgr_program():
    import jax

    def ptpu_jpeg_to_bgr(img, grayscale):  # its name in a profile
        return img[..., 0] if grayscale else img[..., ::-1]

    return jax.jit(ptpu_jpeg_to_bgr, static_argnums=1)


def to_stored_order(img, grayscale):
    """Stage-2's RGB ``(n, h, w, 3)`` batch → cv2's stored order: BGR, or the one
    plane of a grayscale field. A ``ptpu.decode.launch`` of its own."""
    from petastorm_tpu.trace import phase

    with phase("decode.launch"):
        return _to_bgr_program()(img, grayscale)


def decode_jpeg_batch(planes_list, sharding=None):
    """Batched stage 2: list of :class:`JpegPlanes` → (n, h, w, 3) uint8 ``jax.Array``.

    All images must share height/width (resize on write, or decode mixed sizes
    through :func:`decode_jpeg_crop_batch`); mixed chroma samplings are grouped and
    decoded per-group, then re-gathered in input order on device.

    ``sharding``: optional batch-axis sharding (e.g. the loader's). Coefficient slabs
    are placed across its devices before the stage-2 jit, so decode runs SPMD — one
    batch shard per device — and the output is already laid out for consumption
    (single-layout batches; mixed-layout re-gathers may reshard)."""
    import jax.numpy as jnp

    from petastorm_tpu.trace import count

    if not planes_list:
        raise ValueError("decode_jpeg_batch: empty batch")
    sizes = {(p.height, p.width) for p in planes_list}
    if len(sizes) > 1:
        raise ValueError(
            "decode_jpeg_batch requires a uniform image size per batch, got %s. "
            "Pass DataLoader(device_decode_resize=...) to decode mixed sizes in "
            "canvases and resize them on the device, resize on write, or decode on "
            "host via CompressedImageCodec.decode." % sizes
        )
    count("decode_batches")
    groups = {}
    for i, p in enumerate(planes_list):
        groups.setdefault(_layout_key(p), []).append(i)
    if len(groups) == 1:
        layout, = groups
        return _decode_group(layout, planes_list, sharding=sharding)
    parts = []
    order = []
    for layout, indices in groups.items():
        parts.append(_decode_group(layout, [planes_list[i] for i in indices],
                                   sharding=sharding))
        order.extend(indices)
    stacked = jnp.concatenate(parts, axis=0)
    inverse = np.argsort(np.asarray(order))
    return stacked[jnp.asarray(inverse)]


#: Coarse zigzag-prefix buckets: few distinct compiled variants (compile churn is the
#: real cost — each (layout, ks) is a full XLA program), still 75%/50% H2D savings.
#: 64 means "ship the full spectrum for this component" (no pack, no device permute).
_K_BUCKETS = (16, 32, 64)

#: Per-layout sticky buckets: ks only ever GROWS, so content variation across row
#: groups costs at most len(_K_BUCKETS)-1 recompiles per component over the process
#: lifetime instead of one per distinct kmax. Updated from loader transfer threads —
#: the compare-and-grow must be atomic or two concurrent loaders can interleave
#: read-modify-write and transiently shrink a layout's ks (ADVICE r2: extra XLA
#: recompiles, though never wrong output).
_STICKY_KS: dict = {}
_STICKY_KS_LOCK = threading.Lock()


def _truncation_ks(group, layout=None):
    """Per-component zigzag-prefix buckets for a same-layout group, or None when
    truncation is unavailable (a row without kmax) or useless (every component at
    full width)."""
    kms = [p.kmax for p in group]
    if any(km is None for km in kms):
        return None
    ncomp = len(group[0].components)
    return _sticky_ks([max(km[c] for km in kms) + 1 for c in range(ncomp)], layout)


def _sticky_ks(kcounts, layout=None):
    """Per-component coefficient counts → their ``_K_BUCKETS``, grown to the
    largest seen for ``layout``; None when every component ships all 64."""

    def bucket(kcount):
        for b in _K_BUCKETS:
            if kcount <= b:
                return b
        return 64

    ks = [bucket(k) for k in kcounts]
    if layout is not None:
        with _STICKY_KS_LOCK:
            prev = _STICKY_KS.get(layout)
            if prev is not None:
                ks = [max(a, b) for a, b in zip(ks, prev)]
            _STICKY_KS[layout] = ks
    if all(k >= 64 for k in ks):
        return None
    return tuple(ks)


#: Per-(layout, component) components observed to exceed the 12-bit coefficient
#: range: packing is disabled STICKY for them (one overflow means the content class
#: can overflow again — flip-flopping would churn XLA recompiles). Guarded by the
#: same lock as _STICKY_KS.
_PACK12_DISABLED: set = set()

#: Per-layout sticky split points: layout → list of per-component ``(k1, k2)``.
#: Like _STICKY_KS, both only ever GROW (larger = wider tiers = always safe), so
#: content variation across row groups costs a bounded number of XLA recompiles.
#: Guarded by _STICKY_KS_LOCK.
_STICKY_SPLIT: dict = {}

#: Per-(layout, component) split-pack disablement: a range failure here means the
#: specmax bound was violated (mixed provenance rows) — fall back to pack12 sticky.
_SPLIT_DISABLED: set = set()

#: Cumulative coefficient-transfer accounting: ``raw`` = what full int16 coefficients
#: would ship, ``shipped`` = actual bytes after truncation/split/pack. Lets bench
#: artifacts report the REALIZED byte reduction, not the modeled one. Guarded by
#: _STICKY_KS_LOCK.
_TRANSFER_BYTES = {"shipped": 0, "raw": 0}


def transfer_byte_counters(reset=False):
    """Snapshot (optionally reset) the cumulative coefficient-transfer accounting:
    ``{"shipped": bytes_actually_shipped, "raw": int16_equivalent_bytes}``."""
    with _STICKY_KS_LOCK:
        out = dict(_TRANSFER_BYTES)
        if reset:
            _TRANSFER_BYTES["shipped"] = 0
            _TRANSFER_BYTES["raw"] = 0
    return out


def _batch_specmax(group):
    """The group's combined ``(ncomp, 64)`` spectral range profile, or None when any
    row lacks one. Rows of one row group share the profile ARRAY, so the common case
    is a single identity check; mixed-parent groups take the elementwise max."""
    vecs = []
    seen = set()
    for p in group:
        sm = p.specmax
        if sm is None:
            return None
        if id(sm) not in seen:
            seen.add(id(sm))
            vecs.append(sm)
    return vecs[0] if len(vecs) == 1 else np.maximum.reduce(vecs)


def _split_points(profile, ks, layout, align=4):
    """Per-component spectral split ``(k1, k2)`` (or None = plain pack12 is as good)
    from the measured per-position ranges. Positions ≥ k1 fit int8, positions ≥ k2
    fit 4 bits; both bucketed to multiples of ``align`` (pack alignment + bounded
    recompiles; 8 keeps every slab's rows whole 32-bit words) and sticky-grown per
    layout."""
    ncomp = profile.shape[0]
    out = []
    for ci in range(ncomp):
        k = ks[ci] if ks is not None else 64
        mx = profile[ci]

        def low_bound(lim):
            j = k
            while j > 0 and mx[j - 1] <= lim:
                j -= 1
            return j

        k1 = min(-(-low_bound(127) // align) * align, k)
        k2 = min(max(-(-low_bound(7) // align) * align, k1), k)
        out.append((k1, k2))
    with _STICKY_KS_LOCK:
        prev = _STICKY_SPLIT.get(layout)
        if prev is not None:
            out = [(max(a1, b1), max(max(a2, b2), max(a1, b1)))
                   for (a1, a2), (b1, b2) in zip(out, prev)]
        _STICKY_SPLIT[layout] = out
    spec = []
    for ci, (k1, k2) in enumerate(out):
        k = ks[ci] if ks is not None else 64
        k1, k2 = min(k1, k), min(k2, k)
        # k1 == k means every position needs 12 bits: identical bytes to pack12,
        # without its natural-order no-permute fast path — use pack12 instead
        spec.append(None if k1 >= k else (k1, k2))
    return spec


def _stage_inputs(tree, sharding, n):
    """Explicit async ``device_put`` of host staging slabs ahead of the stage-2 jit.

    With a batch-axis ``sharding`` (trailing axes replicated) the decode runs SPMD
    over every device instead of serializing on the default chip (VERDICT r3 #2: on
    a pod host with 4–8 local chips, single-device dispatch makes one chip the decode
    bottleneck while its siblings idle, then pays an extra D2D hop at assembly); an
    indivisible batch falls back to the default device — correct, just unscaled.
    Either way the H2D enqueues immediately — before jit dispatch overhead — so the
    next batch's transfer overlaps the current batch's decode and the jit receives
    device-resident buffers (donation evaluated and rejected: see
    ``_batched_stage2``). Returns ``(tree, spmd)``: ``spmd`` is the sharding
    the stage-2 program must run under, None when it runs on one device.

    The put goes through :func:`~petastorm_tpu.io.staging.put_program_inputs`,
    so a loader decoding a leased batch learns which inputs read borrowed
    memory (only the no-native :func:`stack_jpeg_coefficients` slices can)."""
    import jax

    from petastorm_tpu.io.staging import put_program_inputs
    from petastorm_tpu.parallel.mesh import batch_axis_shard_count

    shards = batch_axis_shard_count(sharding)
    if shards <= 1 or n % shards != 0:
        return put_program_inputs(tree, jax.device_put), None
    import jax.sharding as jsh

    axis = sharding.spec[0]

    def put(a):
        spec = jsh.PartitionSpec(axis, *([None] * (a.ndim - 1)))
        return jax.device_put(a, jsh.NamedSharding(sharding.mesh, spec))

    spmd = jsh.NamedSharding(sharding.mesh, jsh.PartitionSpec(axis))
    return put_program_inputs(
        tree, lambda t: jax.tree_util.tree_map(put, t)), spmd


def _decode_group(layout, group, sharding=None):
    """One same-layout group → device decode, in three phases on the profiler's
    clock: ``ptpu.decode.pack`` (narrow on the host, :func:`_pack_group`, with
    ``threads=`` and ``runs=``), ``ptpu.decode.put`` (the coefficient H2D,
    ``bytes=`` shipped) and ``ptpu.decode.launch`` (the stage-2 call,
    ``new_variant=1`` when it built a new program: the call then compiles or
    loads it).

    ``sharding``: optional batch-axis sharding; staged inputs are placed across its
    devices so dequant+IDCT+upsample+color runs SPMD (one shard of the batch per
    device) instead of on the default device only."""
    from petastorm_tpu.trace import count, phase

    blocks = len(group) * sum(by * bx for _h, _v, by, bx in layout[2])
    count("decode_real_blocks", blocks)
    count("decode_canvas_blocks", blocks)
    count("decode_launches")
    with phase("decode.pack") as span:
        variant, shipped, qtabs, shipped_bytes = _pack_group(layout, group, span)
    with phase("decode.put", bytes=shipped_bytes):
        (shipped, qtabs), spmd = _stage_inputs((shipped, qtabs), sharding, len(group))
    with phase("decode.launch") as span:
        misses = _batched_stage2.cache_info().misses
        program = _batched_stage2(layout, *variant, spmd)
        if span is not None and _batched_stage2.cache_info().misses != misses:
            span.set_metadata(new_variant=1)
        return program(shipped, qtabs)


#: Rows a pack thread takes at the least, and the most threads one pack fans out
#: to: packing is memory-bound, and past a few threads (4-8 on an 8-core host)
#: more only add start-up and contend with the pool's decode threads.
_PACK_ROWS_PER_THREAD = 32
_PACK_MAX_THREADS = 8


def _pack_threads(n):
    """Threads that pack an ``n``-row group: one per slice of at least
    ``_PACK_ROWS_PER_THREAD`` rows, at most the host's cores and
    ``_PACK_MAX_THREADS``; a small group packs on the calling thread."""
    return max(1, min(n // _PACK_ROWS_PER_THREAD, os.cpu_count() or 1,
                      _PACK_MAX_THREADS))


def _source_runs(group):
    """The group's rows as maximal runs of consecutive rows of one source buffer:
    ``[(coeffs, qtabs, start, count), ...]`` in row order, ``coeffs`` one ``(m,
    nblocks, 64)`` int16 array per component and ``qtabs`` ``(m, ncomp, 64)``.
    Rows of a row-group batch decode read their parent's stacked buffers (a run
    ends where the batch crosses to another row group or skips a row); a row
    without a ``batch_ref`` (per-image fallback decode) is a run of its own."""
    runs = []
    for p in group:
        ref = p.batch_ref
        if ref is not None:
            last = runs[-1] if runs else None
            if last is not None and last[0] is ref[0] \
                    and last[2] + last[3] == ref[2]:
                runs[-1] = (last[0], last[1], last[2], last[3] + 1)
            else:
                runs.append((ref[0], ref[1], ref[2], 1))
            continue
        coeffs = tuple(np.ascontiguousarray(c.blocks, dtype=np.int16).reshape(1, -1, 64)
                       for c in p.components)
        runs.append((coeffs, np.stack([c.qtable for c in p.components])[None], 0, 1))
    return runs


def _pack_group(layout, group, span=None):
    """Narrow what a same-layout group ships. Transfer narrowing, exact
    and composable: (a) ship only the zigzag prefix when the batch's kmax says the
    rest of the spectrum is zero; (b) split what ships into per-position bit widths
    from the row group's measured spectral ranges (12-bit head / int8 mid / 4-bit
    tail); (c) 12-bit-pack components the split can't help. Sharp photographic
    content defeats (a) (kmax ≈ 63) but (b) still halves the 12-bit bytes — high
    zigzag positions are heavily quantized; smooth content composes (a)+(b).

    The native packer builds each component's shipped slabs in one pass straight
    from the rows' own coefficients (their row groups' stacked buffers, or a lone
    row's blocks: :func:`_source_runs`), over :func:`_pack_threads` threads; no
    stacked or truncated copy is made. ``span`` (the ``ptpu.decode.pack`` span, or
    None) gets ``threads=`` and ``runs=``.

    Returns ``(variant, shipped, qtabs, shipped_bytes)``: ``variant`` is the
    ``(ks, packed, split)`` of the stage-2 program that reads ``shipped``."""
    from petastorm_tpu.ops import native

    if not native.native_available():
        coeffs, qtabs = stack_jpeg_coefficients(group)
        # un-narrowed transfer still counts: ratio must read ~1.0 here, not "no data"
        full = sum(c.nbytes for c in coeffs)
        with _STICKY_KS_LOCK:
            _TRANSFER_BYTES["raw"] += full
            _TRANSFER_BYTES["shipped"] += full
        return (None, None, None), coeffs, qtabs, full
    runs = _source_runs(group)
    ncomp = len(layout[2])
    n = len(group)
    threads = _pack_threads(n)
    if span is not None:
        span.set_metadata(threads=threads, runs=len(runs))
    qt = np.concatenate([q[s:s + m] for _c, q, s, m in runs])
    qtabs = tuple(qt[:, ci] for ci in range(ncomp))
    ks = _truncation_ks(group, layout)
    profile = _batch_specmax(group)
    if profile is None:
        # Some row lacks a stage-1 profile (per-image fallback decode merged into the
        # group): recover the split savings with one memory-bound pass a run instead
        # of forfeiting them. Past a component's k every row reads zero (kmax).
        profile = np.stack([
            np.maximum.reduce([native.jpeg_specmax_native(c[ci][s:s + m])
                               for c, _q, s, m in runs])
            for ci in range(ncomp)])
    sources = [(c, s, m) for c, _q, s, m in runs]
    variant, shipped, shipped_bytes = _narrow(
        layout, ks, profile,
        lambda tiers: native.jpeg_pack_runs_native(sources, tiers, threads),
        sum(n * by * bx * 64 * 2 for _h, _v, by, bx in layout[2]))
    return variant, shipped, qtabs, shipped_bytes


def _narrow(layout, ks, profile, pack, raw_bytes, align=4):
    """Pack every component at its narrowest exact tier: the zigzag prefix ``ks``,
    the split points :func:`_split_points` reads off ``profile``, else 12 bits,
    sticky per ``(layout, component)``; a range failure widens that component's
    tier (split → 12-bit → int16) for good; ``align`` rounds the split points.
    ``pack(tiers)`` packs the components whose tier is not None, None where one
    failed. Returns ``(variant, shipped,
    shipped_bytes)``, ``variant`` the ``(ks, packed, split)`` that reads them."""
    ncomp = len(profile)
    split = [None] * ncomp
    candidate = _split_points(profile, ks, layout, align)
    with _STICKY_KS_LOCK:
        for ci, s in enumerate(candidate):
            if s is not None and (layout, ci) not in _SPLIT_DISABLED:
                split[ci] = s
        packed = [split[ci] is None and (layout, ci) not in _PACK12_DISABLED
                  for ci in range(ncomp)]
    kk = ks if ks is not None else (64,) * ncomp
    tiers = [(kk[ci], split[ci], packed[ci]) for ci in range(ncomp)]
    shipped = pack(tiers)
    for ci in range(ncomp):
        while shipped[ci] is None:  # a range failure: one tier wider, sticky
            with _STICKY_KS_LOCK:
                if split[ci] is not None:
                    # Range exceeded despite the specmax bound: provenance-mixed
                    # rows. Disable the split for this component; pack12 next.
                    split[ci] = None
                    _SPLIT_DISABLED.add((layout, ci))
                    packed[ci] = (layout, ci) not in _PACK12_DISABLED
                else:  # 12-bit range exceeded: int16 for this component
                    packed[ci] = False
                    _PACK12_DISABLED.add((layout, ci))
            tiers[ci] = (kk[ci], split[ci], packed[ci])
            shipped[ci] = pack([t if c == ci else None for c, t in enumerate(tiers)])[ci]
    shipped_bytes = sum(
        sum(a.nbytes for a in s) if isinstance(s, tuple) else s.nbytes
        for s in shipped)
    with _STICKY_KS_LOCK:
        _TRANSFER_BYTES["raw"] += raw_bytes
        _TRANSFER_BYTES["shipped"] += shipped_bytes
    return (ks, tuple(packed), tuple(split)), tuple(shipped), shipped_bytes


# -- mixed sizes: canvas buckets, cropped and resized on the chip ----------------------

#: Canvas edges, in pixels. A batch of mixed sizes decodes in square canvases: an
#: image takes the smallest edge that holds its MCU-padded height and width, so one
#: program runs per (sampling, canvas) bucket instead of one per size. 512 holds
#: both orientations of ImageNet's common 500x375; 1,024 and 2,048 take the tail.
#: In a row group of several sizes, stage 1 sends an image no canvas holds to
#: cv2 (:func:`canvas_holds`; ``decode_host_rows``); one that still arrives (from
#: a row group of its one size) decodes in its own size.
CANVAS_EDGES = (512, 1024, 2048)

#: the fewest rows a bucket pads to: its rows are a compiled shape, so it pads to
#: a power of two (at most the batch's), and the few images of a tail bucket vary
#: batch to batch
_MIN_BUCKET_ROWS = 8

#: Per component, the flat coefficient array's capacity in blocks, and half as
#: much again in an overflow array that ships only when a batch needs it (else a
#: zero array already on the device takes its place). The capacity grows only
#: when a batch outgrows both, to ``_CAP_HEADROOM`` over it rounded up to 8-15 x
#: 2^e: batches that fit reuse the compiled programs. A quarter of overflow let
#: a batch of rare tail images past the warm-up grow it (a recompile in the
#: timed window, on one seed of the original-size ImageNet cell); a half leaves
#: a batch 1.65 times the first one's blocks in. Guarded by _STICKY_KS_LOCK.
_CAPS: dict = {}
_CAP_HEADROOM = 1.1


def _capacity(key, need):
    """The capacity (blocks) of flat array ``key`` for a batch of ``need``."""
    with _STICKY_KS_LOCK:
        cap = _CAPS.get(key, 0)
        if need > cap + cap // 2:
            want = int(need * _CAP_HEADROOM) + 1
            shift = max(0, want.bit_length() - 4)
            cap = -(-want >> shift) << shift
            _CAPS[key] = cap
    return cap


def _mcu_size(planes):
    """The image's MCU-padded ``(height, width)`` in pixels."""
    c = planes.components[0]
    hmax = max(x.h_samp for x in planes.components)
    vmax = max(x.v_samp for x in planes.components)
    return (c.blocks.shape[0] * 8 * vmax // c.v_samp,
            c.blocks.shape[1] * 8 * hmax // c.h_samp)


def _bucket(planes, exact):
    """``(sampling, (canvas_h, canvas_w))`` of an image: its own MCU-padded size
    when the batch has one layout (``exact``), else the smallest square of
    ``CANVAS_EDGES`` that holds it; None when none does."""
    sampling = tuple((c.h_samp, c.v_samp) for c in planes.components)
    ph, pw = _mcu_size(planes)
    if exact:
        return sampling, (ph, pw)
    for edge in CANVAS_EDGES:
        if ph <= edge and pw <= edge:
            return sampling, (edge, edge)
    return None


def canvas_holds(image):
    """True when a canvas of :data:`CANVAS_EDGES` holds the image, given as its
    :class:`JpegPlanes` or its ``(height, width)`` (:func:`jpeg_size`). Every MCU
    size divides the largest edge, so MCU padding takes no size that fits past it."""
    height, width = (image.height, image.width) if isinstance(image, JpegPlanes) \
        else image
    return max(height, width) <= CANVAS_EDGES[-1]


def _grids(sampling, canvas):
    """Each component's ``(blocks_y, blocks_x)`` in a canvas."""
    hmax = max(h for h, _v in sampling)
    vmax = max(v for _h, v in sampling)
    return tuple((canvas[0] * v // (8 * vmax), canvas[1] * h // (8 * hmax))
                 for h, v in sampling)


def _tap_weights(start, length, out_len, src_len, flip=None):
    """``(n, out_len, src_len)`` float32 weights of ``cv2.resize`` INTER_LINEAR
    (half-pixel centres, no antialiasing) of each image's ``length`` source pixels
    from ``start``, its taps clamped to them; ``flip`` (per image, nonzero: mirror)
    reverses the output positions."""
    import jax.numpy as jnp

    idx = jnp.arange(out_len, dtype=jnp.float32)[None, :]
    if flip is not None:
        idx = jnp.where(flip[:, None] > 0, out_len - 1 - idx, idx)
    start, length = start[:, None], length[:, None]
    src = (idx + 0.5) * (length.astype(jnp.float32) / out_len) - 0.5
    i0 = jnp.floor(src)
    frac = src - i0
    i0 = i0.astype(jnp.int32)
    edge = (i0 < 0) | (i0 >= length - 1)
    frac = jnp.where(edge, 0.0, frac)
    i0 = jnp.clip(i0, 0, length - 1)
    i1 = jnp.minimum(i0 + 1, length - 1)
    cols = jnp.arange(src_len, dtype=jnp.int32)[None, None, :]
    return ((1.0 - frac)[..., None] * (cols == (start + i0)[..., None])
            + frac[..., None] * (cols == (start + i1)[..., None]))


def _crop_resample(wy, img, wx):
    """``(n, oh, h)`` row weights and ``(n, ow, w)`` column weights over ``(n, h,
    w, c)`` images → ``(n, oh, ow, c)`` float32: two float32 matmuls at HIGHEST
    precision."""
    import jax.numpy as jnp
    from jax import lax

    out = jnp.einsum("nrh,nhwc->nrwc", wy, img, precision=lax.Precision.HIGHEST)
    return jnp.einsum("nrwc,nsw->nrsc", out, wx, precision=lax.Precision.HIGHEST)


#: a canvas bucket's table of per-image int32 columns: height, width, the output
#: row it lands in, its box (y0, x0, h, w, flip), then per component its first
#: block in the flat array and its block grid, then per component its qtable
_TABLE_HEAD = 8


def _slab_dtypes(variant, ci):
    """The dtypes of component ``ci``'s shipped slabs under ``variant``."""
    ks, packed, split = variant
    if split is not None and split[ci] is not None:
        return (np.uint8, np.int8, np.uint8)
    return (np.uint8,) if packed is not None and packed[ci] else (np.int16,)


@functools.lru_cache(maxsize=None)
def _crop_program(sampling, canvas, rows, variant, out_hw, grayscale):
    """``jit_ptpu_jpeg_crop_resize``: one canvas bucket of a mixed-size batch, from
    the batch's flat coefficient arrays to its rows of the delivered images.

    Each shipped slab arrives as rows of 32-bit words (the flat array, and its
    overflow beside it). Per image of the bucket (``rows`` of them, padding
    included; a table row each) the program gathers its real blocks into the canvas
    (``canvas`` scope), unpacks, dequantizes and inverse-DCTs them, upsamples
    chroma clamped at the image's own edge, converts to RGB, and resamples its box
    to ``out_hw`` with separable bilinear weights built from the box
    (``crop_resize`` scope: two float32 matmuls at HIGHEST precision; the flip
    folds into the column weights). The result, uint8 in stored order (BGR, or the
    one plane of a grayscale field), is scattered into the batch's output rows
    (``acc``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ch, cw = canvas
    oh, ow = out_hw
    hmax = max(h for h, _v in sampling)
    vmax = max(v for _h, v in sampling)
    grids = _grids(sampling, canvas)
    nc = len(sampling)
    ks, packed, split = variant

    def gather(main, over, idx, dtype, blocks):
        width = main.shape[1] * 4 // np.dtype(dtype).itemsize
        if not width:
            return jnp.zeros((rows, blocks, 0), dtype)
        words = jnp.take(jnp.concatenate([main, over]), idx, axis=0)
        return lax.bitcast_convert_type(words, dtype).reshape(rows, blocks, width)

    def ptpu_jpeg_crop_resize(flat, overflow, table, acc):
        height, width, dest = table[:, 0], table[:, 1], table[:, 2]
        y0, x0, bh, bw, flip = (table[:, 3 + j] for j in range(5))
        planes = []
        for ci, ((hs, vs), (cby, cbx)) in enumerate(zip(sampling, grids)):
            first, by, bx = (table[:, _TABLE_HEAD + 3 * ci + j] for j in range(3))
            q0 = _TABLE_HEAD + 3 * nc + 64 * ci
            with jax.named_scope("canvas"):
                yy = jnp.arange(cby, dtype=jnp.int32)[None, :, None]
                xx = jnp.arange(cbx, dtype=jnp.int32)[None, None, :]
                valid = (yy < by[:, None, None]) & (xx < bx[:, None, None])
                idx = jnp.where(valid, first[:, None, None] + yy * bx[:, None, None]
                                + xx, 0).reshape(-1)
                coef = tuple(gather(m, o, idx, dt, cby * cbx) for m, o, dt in
                             zip(flat[ci], overflow[ci], _slab_dtypes(variant, ci)))
            with jax.named_scope("unpack"):
                coef = _unpack_coefficients(
                    coef if len(coef) > 1 else coef[0],
                    ks[ci] if ks is not None else 64,
                    split[ci] if split is not None else None,
                    packed is not None and packed[ci])
                coef = jnp.where(valid.reshape(rows, -1, 1), coef, 0)
            with jax.named_scope("dequant_idct"):
                scaled = coef.astype(jnp.float32) \
                    * table[:, None, q0:q0 + 64].astype(jnp.float32)
                pix = _idct_scaled(scaled.reshape(-1, 64))
                pix = jnp.clip(jnp.round(pix), 0.0, 255.0)
            with jax.named_scope("upsample"):
                plane = pix.reshape(rows, cby, cbx, 8, 8)
                plane = jnp.transpose(plane, (0, 1, 3, 2, 4)).reshape(
                    rows, cby * 8, cbx * 8)
                limits = ((height * vs + vmax - 1) // vmax,
                          (width * hs + hmax - 1) // hmax)
                for axis, r, lim in ((1, vmax // vs, limits[0]),
                                     (2, hmax // hs, limits[1])):
                    if r == 2:
                        plane = _fancy_upsample2(plane, axis, lim)
                    elif r > 1:
                        plane = jnp.repeat(plane, r, axis=axis)
                planes.append(plane[:, :ch, :cw])
        with jax.named_scope("color"):
            img = planes[0][..., None] if nc == 1 else ycbcr_to_rgb(*planes)
            img = jnp.clip(jnp.round(img), 0.0, 255.0)
        with jax.named_scope("crop_resize"):
            out = _crop_resample(_tap_weights(y0, bh, oh, ch), img,
                                 _tap_weights(x0, bw, ow, cw, flip))
            out = jnp.clip(jnp.round(out), 0.0, 255.0).astype(jnp.uint8)
            if grayscale:
                out = out[..., 0]
            elif nc == 1:
                out = jnp.broadcast_to(out, out.shape[:3] + (3,))
            else:
                out = out[..., ::-1]
        return acc.at[dest].set(out, mode="drop")

    return jax.jit(ptpu_jpeg_crop_resize)


@functools.lru_cache(maxsize=32)
def _device_zeros(shape, dtype):
    """A zero array on the default device, made once: the output a batch's first
    bucket scatters into, and the overflow a batch that fits does not ship. Put
    from the host, so that it compiles nothing."""
    import jax

    return jax.device_put(np.zeros(shape, dtype))


_ZERO_BLOCKS = np.zeros((0, 1, 64), np.int16)


def _zero_blocks(m):
    """``(m, 1, 64)`` int16 zeros, from one buffer that grows."""
    global _ZERO_BLOCKS
    if len(_ZERO_BLOCKS) < m:
        _ZERO_BLOCKS = np.zeros((max(m, 2 * len(_ZERO_BLOCKS)), 1, 64), np.int16)
    return _ZERO_BLOCKS


def _block_runs(images, ci):
    """Component ``ci`` of ``images`` as runs of ``(m, 1, 64)`` int16 blocks:
    ``[(src, start, count), ...]``, each row-group row a slice of its parent's
    stacked buffer (consecutive rows of one parent merge), each other row its own."""
    runs = []
    for p in images:
        ref = p.batch_ref
        comp = p.components[ci]
        nb = comp.blocks.shape[0] * comp.blocks.shape[1]
        if ref is not None:
            parent = ref[0][ci]
            last = runs[-1] if runs else None
            if last is not None and last[3] is parent and last[1] + last[2] == ref[2] * nb:
                runs[-1] = (last[0], last[1], last[2] + nb, parent)
            else:
                runs.append((parent.reshape(-1, 1, 64), ref[2] * nb, nb, parent))
            continue
        src = np.ascontiguousarray(comp.blocks, dtype=np.int16).reshape(-1, 1, 64)
        runs.append((src, 0, nb, None))
    return [(src, start, count) for src, start, count, _p in runs]


def _profile(p, ci):
    """Image ``p``'s per-zigzag-position max |coefficient| of component ``ci``."""
    from petastorm_tpu.ops import native

    if p.specmax is not None:
        return p.specmax[ci]
    return native.jpeg_specmax_native(
        np.ascontiguousarray(p.components[ci].blocks, dtype=np.int16).reshape(1, -1, 64))


def _words(slab):
    """A packed slab's rows as 32-bit words (a view)."""
    return slab.reshape(slab.shape[0], -1).view(np.uint32)


def _pack_flat(images, ncomp, threads):
    """Every image's real blocks, component by component, in one flat array each
    (the images in order), zero-padded to its capacity (:func:`_capacity`; blocks
    past it in an overflow array of half of it) and narrowed like a layout
    group's, with split points on 8 so that every slab's rows are 32-bit words.

    Returns ``(variant, flat, overflow, offsets, shipped_bytes, real_blocks)``:
    per component a tuple of ``(capacity, words)`` uint32 slabs, and of the overflow
    slabs (None where the batch fits), and each image's first block
    (``offsets[ci][j]``)."""
    from petastorm_tpu.ops import native

    key = ("canvas", ncomp)
    offsets, runs, caps, sizes_ = [], [], [], []
    real = 0
    for ci in range(ncomp):
        sizes = np.array([math.prod(p.components[ci].blocks.shape[:2])
                          if len(p.components) > ci else 0 for p in images], np.int64)
        offsets.append(np.cumsum(sizes) - sizes)
        total = int(sizes.sum())
        real += total
        cap = _capacity((key, ci), total)
        size = cap if total <= cap else cap + cap // 2
        run = _block_runs([p for p in images if len(p.components) > ci], ci)
        if size > total:
            run.append((_zero_blocks(size - total), 0, size - total))
        runs.append(run)
        caps.append(cap)
        sizes_.append(size)
    if not native.native_available():
        shipped = [np.concatenate([src[s:s + m] for src, s, m in run]) for run in runs]
        variant = (None, None, None)
    else:
        profile = np.stack([
            np.maximum.reduce([_profile(p, ci) for p in images if len(p.components) > ci])
            for ci in range(ncomp)])
        kcounts = [int(np.flatnonzero(row).max()) + 1 if row.any() else 1
                   for row in profile]
        ks = _sticky_ks(kcounts, key)

        def pack(tiers):
            return [None if t is None else native.jpeg_pack_runs_native(
                [((src,), s, m) for src, s, m in runs[ci]], [t], threads)[0]
                for ci, t in enumerate(tiers)]

        variant, shipped, _nbytes = _narrow(key, ks, profile, pack,
                                            sum(sizes_) * 128, align=8)
    flat, overflow, shipped_bytes = [], [], 0
    for ci, s in enumerate(shipped):
        slabs = tuple(_words(a) for a in (s if isinstance(s, tuple) else (s,)))
        shipped_bytes += sum(a.nbytes for a in slabs)
        flat.append(tuple(a[:caps[ci]] for a in slabs))
        overflow.append(tuple(a[caps[ci]:] for a in slabs)
                        if sizes_[ci] > caps[ci] else None)
    return variant, flat, overflow, offsets, shipped_bytes, real


def decode_jpeg_crop_batch(planes_list, boxes, size, grayscale=False):
    """Mixed-size stage 2 with a crop and resize: :class:`JpegPlanes` rows and
    their ``(n, 5)`` int32 boxes (``y0, x0, h, w, flip``; see
    :mod:`petastorm_tpu.ops.crop`) → one ``(m, *size, 3)`` uint8 device array in
    stored (BGR) order, or ``(m, *size)`` for a ``grayscale`` field.

    The rows group by (sampling, canvas) (:data:`CANVAS_EDGES`; a batch of one
    layout, and an image no canvas holds, decode in their own size, with no
    padding). Their real coefficient
    blocks ship as one flat array per component (``decode.pack`` then
    ``decode.put``, ``canvas=`` naming each bucket's canvas and images), and one
    ``jit_ptpu_jpeg_crop_resize`` launch a bucket (``decode.launch``) decodes the
    bucket in its canvases, crops, resizes and flips each image by its box and
    writes its rows of the output, in order.
    """
    from petastorm_tpu.trace import count, phase

    boxes = np.asarray(boxes, np.int32)
    exact = len({_layout_key(p) for p in planes_list}) == 1
    buckets = {}
    for i, p in enumerate(planes_list):
        buckets.setdefault(_bucket(p, exact) or _bucket(p, True), []).append(i)
    count("decode_batches")
    n = len(planes_list)
    order = [i for rows in buckets.values() for i in rows]
    images = [planes_list[i] for i in order]
    ncomp = max(len(p.components) for p in images)
    batch_rows = 1 << (n - 1).bit_length()
    desc = ",".join("%dx%d:%d" % (c[0], c[1], len(r)) for (_s, c), r in buckets.items())
    with phase("decode.pack", canvas=desc) as span:
        threads = _pack_threads(len(images))
        if span is not None:
            span.set_metadata(threads=threads)
        variant, flat, overflow, offsets, shipped_bytes, real = _pack_flat(
            images, ncomp, threads)
        at = {i: j for j, i in enumerate(order)}
        tables, programs, canvas_blocks = [], [], 0
        for (sampling, canvas), rows in buckets.items():
            nc = len(sampling)
            n_pad = min(batch_rows,
                        max(_MIN_BUCKET_ROWS, 1 << (len(rows) - 1).bit_length()))
            table = np.zeros((n_pad, _TABLE_HEAD + 67 * nc), np.int32)
            table[:, 2] = n  # padding rows land nowhere,
            table[:, 5:7] = 1  # from a 1x1 box of an empty canvas
            for r, i in enumerate(rows):
                p = planes_list[i]
                table[r, :3] = p.height, p.width, i
                table[r, 3:8] = boxes[i]
                for ci, c in enumerate(p.components):
                    col = _TABLE_HEAD + 3 * ci
                    table[r, col:col + 3] = (offsets[ci][at[i]], c.blocks.shape[0],
                                             c.blocks.shape[1])
                    q0 = _TABLE_HEAD + 3 * nc + 64 * ci
                    table[r, q0:q0 + 64] = c.qtable
            tables.append(table)
            programs.append((sampling, canvas, n_pad,
                             tuple(x[:nc] if x is not None else None for x in variant)))
            canvas_blocks += n_pad * sum(y * x for y, x in _grids(sampling, canvas))
    count("decode_real_blocks", real)
    count("decode_canvas_blocks", canvas_blocks)
    shipped = (tuple(flat), tuple(o for o in overflow if o is not None), tuple(tables))
    with phase("decode.put",
               bytes=shipped_bytes + sum(t.nbytes for t in tables)):
        (flat, shipped_over, tables), _spmd = _stage_inputs(shipped, None, len(images))
    shipped_over = iter(shipped_over)
    overflow = tuple(next(shipped_over) if o is not None else tuple(
        _device_zeros((a.shape[0] // 2, a.shape[1]), np.uint32) for a in f)
        for o, f in zip(overflow, flat))
    out = _device_zeros((n,) + tuple(size) + (() if grayscale else (3,)),
                        np.uint8)
    for (sampling, canvas, n_pad, sub), table in zip(programs, tables):
        nc = len(sampling)
        with phase("decode.launch", canvas="%dx%d" % canvas, rows=n_pad) as span:
            misses = _crop_program.cache_info().misses
            program = _crop_program(sampling, canvas, n_pad, sub, tuple(size), grayscale)
            if span is not None and _crop_program.cache_info().misses != misses:
                span.set_metadata(new_variant=1)
            out = program(flat[:nc], overflow[:nc], table, out)
        count("decode_launches")
    return out
