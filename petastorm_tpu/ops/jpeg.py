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

import dataclasses
import functools
import os
import struct
import threading

import numpy as np

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


def _fancy_upsample2(plane, axis):
    """libjpeg 'fancy' 2x upsampling along ``axis``: triangle filter (3*near + far) / 4,
    edges clamped — matches libjpeg/cv2 output much closer than pixel doubling."""
    import jax.numpy as jnp

    plane = jnp.moveaxis(plane, axis, 0)
    prev = jnp.concatenate([plane[:1], plane[:-1]], axis=0)
    nxt = jnp.concatenate([plane[1:], plane[-1:]], axis=0)
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
    run stage-1 decode truly in parallel. Raises ValueError on streams the two-stage
    path cannot handle (lossless/arithmetic, CMYK, corrupt) — the codec layer catches that and
    falls back to full host decode per stream."""
    from petastorm_tpu.ops import native

    if native.native_available():
        height, width, comps = native.jpeg_decode_coeffs_native(data)
        planes = JpegPlanes(
            height=height,
            width=width,
            components=[JpegComponent(blocks, qtable, h, v)
                        for blocks, qtable, h, v in comps],
        )
    else:
        planes = entropy_decode_jpeg(data)
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
    buffers (no per-image ctypes overhead, no copies, GIL released throughout); each
    returned ``JpegPlanes`` holds zero-copy views into those buffers plus a ``batch_ref``
    so downstream batching can slice the parent arrays directly.

    Raises RuntimeError when the native decoder is unavailable and ValueError when the
    first stream has no usable baseline layout (callers fall back to the per-image path).
    """
    from petastorm_tpu.ops import native

    if not native.native_available():
        raise RuntimeError("native jpeg decoder unavailable: %s" % native.native_error())
    layout, coeffs, qtabs, kmax, status = native.jpeg_decode_coeffs_batch_native(blobs)
    height, width, comps_layout = layout
    if len(comps_layout) not in (1, 3):
        raise ValueError(
            "Unsupported JPEG component count %d (expected 1 or 3)" % len(comps_layout)
        )
    qtabs = qtabs.astype(np.int32)  # per-image contract dtype (one cast per row group)
    # Spectral range profile, one native pass per component over the stacked buffers
    # (memory-bound, GIL released; failed streams' slices are zeroed so they cannot
    # inflate it). Shared across the group's rows — drives the split-pack transfer.
    specmax = np.stack([native.jpeg_specmax_native(c) for c in coeffs])
    out = []
    for i in range(len(blobs)):
        if status[i] != 0:
            out.append(None)
            continue
        comps = [
            JpegComponent(coeffs[c][i].reshape(by, bx, 64), qtabs[i, c], h, v)
            for c, (h, v, by, bx) in enumerate(comps_layout)
        ]
        out.append(JpegPlanes(height, width, comps, batch_ref=(coeffs, qtabs, i),
                              kmax=kmax, specmax=specmax))
    return out


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
    # HOST constant, deliberately: a device array closed over by ``fn`` would be
    # lowered via a D2H fetch at every new layout variant's compile — measured
    # MINUTES when that fetch queues behind in-flight transfers on a degraded
    # service (r4 bench hang, faulthandler: _array_mlir_constant_handler → _value)
    unzig = np.asarray(UNZIGZAG)

    def unpack12(u8):
        # (n, blocks, m*3) uint8 → (n, blocks, 2m) int32, 12-bit two's complement
        triples = u8.reshape(u8.shape[0], u8.shape[1], -1, 3)
        b0 = triples[..., 0].astype(jnp.int32)
        b1 = triples[..., 1].astype(jnp.int32)
        b2 = triples[..., 2].astype(jnp.int32)
        lo = b0 | ((b1 & 0xF) << 8)
        hi = (b1 >> 4) | (b2 << 4)
        pair = jnp.stack([lo, hi], axis=-1)
        pair = pair - ((pair & 0x800) << 1)  # sign-extend 12-bit
        return pair.reshape(u8.shape[0], u8.shape[1], -1)

    def unpack4(u8):
        # (n, blocks, m) uint8 → (n, blocks, 2m) int32, 4-bit two's complement
        b = u8.astype(jnp.int32)
        lo = b & 0xF
        hi = (b >> 4) & 0xF
        pair = jnp.stack([lo, hi], axis=-1)
        pair = pair - ((pair & 0x8) << 1)  # sign-extend 4-bit
        return pair.reshape(u8.shape[0], u8.shape[1], -1)

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
            k_ship = ks[ci] if ks is not None else 64
            with jax.named_scope("unpack"):
                if split is not None and split[ci] is not None:
                    head, mid, tail = coef
                    parts = []
                    if head.shape[-1]:
                        parts.append(unpack12(head))
                    if mid.shape[-1]:
                        parts.append(mid.astype(jnp.int32))
                    if tail.shape[-1]:
                        parts.append(unpack4(tail))
                    coef = parts[0] if len(parts) == 1 \
                        else jnp.concatenate(parts, axis=-1)
                    if k_ship < 64:
                        coef = jnp.pad(coef, ((0, 0), (0, 0), (0, 64 - k_ship)))
                    coef = jnp.take(coef, unzig, axis=-1)
                else:
                    if packed is not None and packed[ci]:
                        coef = unpack12(coef)
                    if ks is not None and ks[ci] < 64:
                        coef = jnp.pad(coef, ((0, 0), (0, 0), (0, 64 - ks[ci])))
                        coef = jnp.take(coef, unzig, axis=-1)
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


def resize_image_batch(img, target):
    """(n, h, w, c) uint8 device batch → (n, *target, c), bilinear, no antialiasing
    (tracks ``cv2.resize(..., INTER_LINEAR)``, the reference host resize idiom —
    identical sampling grid on upscale, same no-prefilter choice on downscale; values
    differ from cv2 only by float rounding). No-op when already at ``target``."""
    import jax
    import jax.numpy as jnp

    h, w = int(target[0]), int(target[1])
    if img.shape[1] == h and img.shape[2] == w:
        return img
    out = jax.image.resize(
        img.astype(jnp.float32), (img.shape[0], h, w, img.shape[3]),
        method="linear", antialias=False)
    return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)


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


def decode_jpeg_batch(planes_list, resize_to=None, sharding=None):
    """Batched stage 2: list of :class:`JpegPlanes` → (n, h, w, 3) uint8 ``jax.Array``.

    Without ``resize_to`` all images must share height/width (resize on write, or use
    padded-shape fields); mixed chroma samplings are grouped and decoded per-group,
    then re-gathered in input order on device.

    ``resize_to=(h, w)`` lifts the uniform-size requirement for mixed-size stores
    (raw ImageNet-style corpora): each same-layout group decodes at its stored size
    and is bilinearly resized ON DEVICE to the target (``resize_image_batch``), so
    every batch leaves with one static shape regardless of composition.

    ``sharding``: optional batch-axis sharding (e.g. the loader's). Coefficient slabs
    are placed across its devices before the stage-2 jit, so decode runs SPMD — one
    batch shard per device — and the output is already laid out for consumption
    (single-layout batches; mixed-layout re-gathers may reshard)."""
    import jax.numpy as jnp

    if not planes_list:
        raise ValueError("decode_jpeg_batch: empty batch")
    sizes = {(p.height, p.width) for p in planes_list}
    if len(sizes) > 1 and resize_to is None:
        raise ValueError(
            "decode_jpeg_batch requires a uniform image size per batch, got %s. "
            "Pass resize_to=(h, w) (DataLoader(device_decode_resize=...)) to decode "
            "mixed sizes with an on-device resize, resize on write, or decode on "
            "host via CompressedImageCodec.decode." % sizes
        )
    groups = {}
    for i, p in enumerate(planes_list):
        groups.setdefault(_layout_key(p), []).append(i)
    if len(groups) == 1:
        layout, = groups
        out = _decode_group(layout, planes_list, sharding=sharding)
        return resize_image_batch(out, resize_to) if resize_to is not None else out
    parts = []
    order = []
    for layout, indices in groups.items():
        group = [planes_list[i] for i in indices]
        decoded = _decode_group(layout, group, sharding=sharding)
        if resize_to is not None:
            decoded = resize_image_batch(decoded, resize_to)
        parts.append(decoded)
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

    def bucket(kcount):
        for b in _K_BUCKETS:
            if kcount <= b:
                return b
        return 64

    ks = [bucket(max(km[c] for km in kms) + 1) for c in range(ncomp)]
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


def _round_up4(x):
    return (x + 3) & ~3


def _split_points(profile, ks, layout):
    """Per-component spectral split ``(k1, k2)`` (or None = plain pack12 is as good)
    from the measured per-position ranges. Positions ≥ k1 fit int8, positions ≥ k2
    fit 4 bits; both bucketed to multiples of 4 (pack alignment + bounded recompiles)
    and sticky-grown per layout."""
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

        k1 = min(_round_up4(low_bound(127)), k)
        k2 = min(max(_round_up4(low_bound(7)), k1), k)
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
    from petastorm_tpu.trace import phase

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
    split = [None] * ncomp
    candidate = _split_points(profile, ks, layout)
    with _STICKY_KS_LOCK:
        for ci, s in enumerate(candidate):
            if s is not None and (layout, ci) not in _SPLIT_DISABLED:
                split[ci] = s
        packed = [split[ci] is None and (layout, ci) not in _PACK12_DISABLED
                  for ci in range(ncomp)]
    kk = ks if ks is not None else (64,) * ncomp
    tiers = [(kk[ci], split[ci], packed[ci]) for ci in range(ncomp)]
    sources = [(c, s, m) for c, _q, s, m in runs]
    shipped = native.jpeg_pack_runs_native(sources, tiers, threads)
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
            shipped[ci] = native.jpeg_pack_runs_native(
                sources, [t if c == ci else None for c, t in enumerate(tiers)],
                threads)[ci]
    raw_bytes = sum(n * by * bx * 64 * 2 for _h, _v, by, bx in layout[2])
    shipped_bytes = sum(
        sum(a.nbytes for a in s) if isinstance(s, tuple) else s.nbytes
        for s in shipped)
    with _STICKY_KS_LOCK:
        _TRANSFER_BYTES["raw"] += raw_bytes
        _TRANSFER_BYTES["shipped"] += shipped_bytes
    return (ks, tuple(packed), tuple(split)), tuple(shipped), qtabs, shipped_bytes
