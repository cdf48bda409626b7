"""Native host kernels: built from C++ at first use with g++, loaded via ctypes.

The runtime tier around the JAX/Pallas compute path (SURVEY.md §3 "native tier"): the
entropy half of JPEG decode is sequential/branchy host work, so it runs as compiled C++
(jpeg_decoder.cpp) rather than the pure-Python oracle. ctypes calls release the GIL, so
the reader thread pool parallelizes stage-1 decode across cores.

Build model: the shared object is compiled once into a cache directory keyed by a hash of
the source (recompile-on-change), with an atomic rename so concurrent processes race
safely. No pybind11 (not in the image); the C ABI + ctypes keeps the binding dependency-free.
Set ``PETASTORM_TPU_DISABLE_NATIVE=1`` to force the Python fallbacks.
"""
from __future__ import annotations

import collections
import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading

logger = logging.getLogger(__name__)

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB = None
_LIB_ERR = None


def _cache_dir():
    root = os.environ.get("PETASTORM_TPU_CACHE")
    if not root:
        root = os.path.join(
            os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")),
            "petastorm_tpu",
        )
    os.makedirs(root, exist_ok=True)
    return root


def _compile(sources, name):
    """Compile C++ sources into a cached .so; returns its path. Raises on failure."""
    hasher = hashlib.sha256()
    for src in sources:
        with open(src, "rb") as f:
            hasher.update(f.read())
    tag = hasher.hexdigest()[:16]
    out_path = os.path.join(_cache_dir(), "%s-%s.so" % (name, tag))
    if os.path.exists(out_path):
        return out_path
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_cache_dir())
    os.close(fd)
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread", "-o", tmp] \
        + list(sources)
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True, timeout=120)
        os.replace(tmp, out_path)  # atomic: concurrent builders converge on one file
    except subprocess.CalledProcessError as e:
        os.unlink(tmp)
        raise RuntimeError("native build failed: %s\n%s" % (" ".join(cmd), e.stderr))
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return out_path


class _JpegLayout(ctypes.Structure):
    _fields_ = [
        ("height", ctypes.c_int32),
        ("width", ctypes.c_int32),
        ("ncomp", ctypes.c_int32),
        ("h_samp", ctypes.c_int32 * 4),
        ("v_samp", ctypes.c_int32 * 4),
        ("blocks_y", ctypes.c_int32 * 4),
        ("blocks_x", ctypes.c_int32 * 4),
    ]


def _load():
    global _LIB, _LIB_ERR
    if _LIB is not None or _LIB_ERR is not None:
        return _LIB
    with _LOCK:
        if _LIB is not None or _LIB_ERR is not None:
            return _LIB
        if os.environ.get("PETASTORM_TPU_DISABLE_NATIVE"):
            _LIB_ERR = "disabled via PETASTORM_TPU_DISABLE_NATIVE"
            return None
        try:
            path = _compile([os.path.join(_SRC_DIR, "jpeg_decoder.cpp")], "ptpu_native")
            lib = ctypes.CDLL(path)
            lib.ptpu_jpeg_error_string.argtypes = [ctypes.c_int]
            lib.ptpu_jpeg_error_string.restype = ctypes.c_char_p
            lib.ptpu_jpeg_parse_layout.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(_JpegLayout)]
            lib.ptpu_jpeg_parse_layout.restype = ctypes.c_int
            lib.ptpu_jpeg_decode_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int32, ctypes.POINTER(_JpegLayout),
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
            lib.ptpu_jpeg_decode_batch.restype = ctypes.c_int32
            lib.ptpu_jpeg_zigzag_truncate.argtypes = [
                ctypes.POINTER(ctypes.c_int16), ctypes.POINTER(ctypes.c_int16),
                ctypes.c_int64, ctypes.c_int32]
            lib.ptpu_jpeg_zigzag_truncate.restype = None
            lib.ptpu_jpeg_pack12.argtypes = [
                ctypes.POINTER(ctypes.c_int16), ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int64]
            lib.ptpu_jpeg_pack12.restype = ctypes.c_int32
            lib.ptpu_jpeg_specmax.argtypes = [
                ctypes.POINTER(ctypes.c_int16), ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
            lib.ptpu_jpeg_specmax.restype = None
            lib.ptpu_jpeg_pack_split.argtypes = [
                ctypes.POINTER(ctypes.c_int16), ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int8),
                ctypes.POINTER(ctypes.c_uint8)]
            lib.ptpu_jpeg_pack_split.restype = ctypes.c_int32
            lib.ptpu_jpeg_pack_rows.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p,
                ctypes.c_int32]
            lib.ptpu_jpeg_pack_rows.restype = ctypes.c_int32
            _LIB = lib
        except Exception as e:  # noqa: BLE001 — degrade to Python fallback
            _LIB_ERR = str(e)
            logger.warning("Native kernels unavailable (%s); using Python fallbacks", e)
        return _LIB


def native_available():
    """True when the compiled decoder loaded (builds it on first call)."""
    return _load() is not None


def native_error():
    """Why native is unavailable (None when it loaded fine)."""
    _load()
    return _LIB_ERR


#: Error codes the decoder maps to ValueError (bad input) vs RuntimeError (internal).
_VALUE_ERRORS = {-1, -2, -3, -4, -5, -6}


def _parse_layout(lib, blob):
    layout = _JpegLayout()
    rc = lib.ptpu_jpeg_parse_layout(blob, len(blob), ctypes.byref(layout))
    if rc != 0:
        _raise(lib, rc)
    return layout


def _raise(lib, rc):
    msg = lib.ptpu_jpeg_error_string(rc).decode()
    if rc in _VALUE_ERRORS:
        raise ValueError(msg)
    raise RuntimeError(msg)


def _layout_tuple(layout):
    return (layout.height, layout.width,
            tuple((layout.h_samp[c], layout.v_samp[c], layout.blocks_y[c],
                   layout.blocks_x[c]) for c in range(layout.ncomp)))


def jpeg_parse_layout_native(data):
    """JPEG bytes → layout tuple ``(height, width, ((h, v, by, bx), ...))`` from the
    frame header only (no entropy decode). ValueError on non-baseline/corrupt headers."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    return _layout_tuple(_parse_layout(lib, bytes(data)))


#: One stage-1 call's result (:func:`jpeg_decode_coeffs_batch_native`).
StageOne = collections.namedtuple(
    "StageOne", "layout coeffs qtabs kmax status specmax symbols fast_symbols")


def _decode(lib, blobs, layout, coeffs):
    """``ptpu_jpeg_decode_batch`` of ``blobs`` into ``coeffs`` (per component, C-
    contiguous int16 with room for every stream's blocks): the other outputs."""
    import numpy as np

    n, ncomp = len(blobs), layout.ncomp
    qtabs = np.empty((n, ncomp, 64), dtype=np.int32)
    kmax = np.empty(4, dtype=np.int32)
    specmax = np.empty((ncomp, 64), dtype=np.int32)
    symbols = np.empty(2, dtype=np.int64)
    status = np.empty(n, dtype=np.int32)
    lib.ptpu_jpeg_decode_batch(
        (ctypes.c_char_p * n)(*blobs), (ctypes.c_int64 * n)(*map(len, blobs)), n,
        ctypes.byref(layout), (ctypes.c_void_p * 4)(*[a.ctypes.data for a in coeffs]),
        qtabs.ctypes.data, kmax.ctypes.data, specmax.ctypes.data, symbols.ctypes.data,
        status.ctypes.data)
    return qtabs, tuple(int(k) for k in kmax[:ncomp]), status, specmax, symbols


def jpeg_decode_coeffs_batch_native(blobs):
    """Entropy-decode a whole row group of same-layout JPEGs in ONE native call.

    Decodes straight into stacked numpy buffers — no per-image ctypes round trip, no
    buffer copies, GIL released for the entire batch.

    Returns a :data:`StageOne`:

    - ``layout``: ``(height, width, ((h_samp, v_samp, blocks_y, blocks_x), ...))``
      parsed from the first stream
    - ``coeffs``: tuple of ``(n, blocks_y*blocks_x, 64)`` int16 arrays, one per component
    - ``qtabs``: ``(n, ncomp, 64)`` int32 natural-order quantization tables
    - ``kmax``: per component, the highest ZIGZAG position any stream holds a nonzero
      coefficient at — transfers may ship only the prefix
      (:func:`jpeg_zigzag_truncate_native`)
    - ``status``: ``(n,)`` int32 — 0 decoded; nonzero = that stream failed
      (lossless/arithmetic mode / corrupt / different layout; its slice is zeroed) and
      the caller must re-decode it individually (e.g. cv2 host fallback). Baseline and
      progressive streams both decode natively.
    - ``specmax``: ``(ncomp, 64)`` int32, per zigzag position the largest |coefficient|
      of the decoded streams (:func:`jpeg_specmax_native` over ``coeffs``), folded
      into the decode
    - ``symbols``, ``fast_symbols``: the Huffman symbols decoded, and those of them
      one table lookup resolved with their magnitude bits

    Raises ValueError when the FIRST stream has no parseable baseline-or-progressive
    layout (caller falls back to per-image decode for the whole batch)."""
    import numpy as np

    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    blobs = [bytes(b) for b in blobs]
    n = len(blobs)
    if n == 0:
        raise ValueError("empty batch")
    layout = _parse_layout(lib, blobs[0])
    coeffs = tuple(
        np.empty((n, layout.blocks_y[c] * layout.blocks_x[c], 64), dtype=np.int16)
        for c in range(layout.ncomp))
    qtabs, kmax, status, specmax, symbols = _decode(lib, blobs, layout, coeffs)
    return StageOne(_layout_tuple(layout), coeffs, qtabs, kmax, status, specmax,
                    int(symbols[0]), int(symbols[1]))


def jpeg_decode_image_native(data):
    """One JPEG's stage 1: its frame header, then one GIL-released decode straight
    into arrays of its own.

    Returns ``(height, width, comps, kmax, specmax, symbols, fast_symbols)``:
    ``comps`` is ``[(blocks, qtable, h_samp, v_samp), ...]`` with ``blocks`` a
    ``(blocks_y, blocks_x, 64)`` int16 natural-order array and ``qtable`` a ``(64,)``
    int32 natural-order row of one ``(ncomp, 64)`` array; the rest as
    :func:`jpeg_decode_coeffs_batch_native` gives them. Raises ValueError on
    malformed/unsupported streams (same contract as the Python oracle) and
    RuntimeError when the native library is unavailable."""
    import numpy as np

    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    blob = bytes(data)
    layout = _parse_layout(lib, blob)
    coeffs = [np.empty((layout.blocks_y[c], layout.blocks_x[c], 64), dtype=np.int16)
              for c in range(layout.ncomp)]
    qtabs, kmax, status, specmax, symbols = _decode(lib, [blob], layout, coeffs)
    if status[0] != 0:
        _raise(lib, int(status[0]))
    comps = [(coeffs[c], qtabs[0, c], layout.h_samp[c], layout.v_samp[c])
             for c in range(layout.ncomp)]
    return (layout.height, layout.width, comps, kmax, specmax, int(symbols[0]),
            int(symbols[1]))


def jpeg_zigzag_truncate_native(src, k):
    """(n, nblocks, 64) int16 natural-order coefficients → (n, nblocks, k) int16
    zigzag-prefix pack (``dst[..., j] = src[..., zigzag_to_natural(j)]``). The caller
    guarantees all coefficients beyond zigzag index k-1 are zero (``kmax`` from the
    batch decode)."""
    import numpy as np

    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    src = np.ascontiguousarray(src, dtype=np.int16)
    n, nb, last = src.shape
    if last != 64:
        raise ValueError("expected trailing dim 64, got %d" % last)
    if not 1 <= int(k) <= 64:  # k > 64 would read past the zigzag table in C
        raise ValueError("k must be in [1, 64], got %r" % (k,))
    dst = np.empty((n, nb, int(k)), dtype=np.int16)
    lib.ptpu_jpeg_zigzag_truncate(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        n * nb, int(k),
    )
    return dst


def jpeg_pack12_native(src):
    """(n, nblocks, k) int16 coefficients → (n, nblocks, k*3//2) uint8 12-bit pack
    (two coefficients per 3 bytes), or None when any value exceeds the 12-bit range
    (the caller ships int16 unpacked). ``k`` must be even. The device side unpacks
    with fused integer ops (`ops.jpeg` stage 2) — H2D ships 75% of the bytes."""
    import numpy as np

    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    src = np.ascontiguousarray(src, dtype=np.int16)
    n, nb, k = src.shape
    if k % 2:
        raise ValueError("pack12 needs an even trailing dim, got %d" % k)
    dst = np.empty((n, nb, k * 3 // 2), dtype=np.uint8)
    rc = lib.ptpu_jpeg_pack12(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n * nb * k,
    )
    return dst if rc == 0 else None


def jpeg_specmax_native(src, is_zigzag=False):
    """(n, nblocks, k) int16 coefficients → (k,) int32 per-zigzag-position max |value|.

    ``is_zigzag`` says rows are zigzag-prefix packs (:func:`jpeg_zigzag_truncate_native`
    output); otherwise rows are natural order and k must be 64. The spectral range
    profile drives the per-position bit-width split (:func:`jpeg_pack_split_native`)."""
    import numpy as np

    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    src = np.ascontiguousarray(src, dtype=np.int16)
    n, nb, k = src.shape
    if not is_zigzag and k != 64:
        raise ValueError("natural-order specmax needs trailing dim 64, got %d" % k)
    out = np.zeros(k, dtype=np.int32)
    lib.ptpu_jpeg_specmax(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        n * nb, k, 1 if is_zigzag else 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out


def jpeg_pack_split_native(src, k1, k2, is_zigzag=False):
    """Spectral-split pack: (n, nblocks, k) int16 → three uint8/int8 slabs with
    per-zigzag-position bit widths (12-bit head [0, k1), int8 mid [k1, k2), 4-bit
    nibble tail [k2, k)), or None when any value exceeds its tier's range (the caller
    falls back to a wider pack). k1 and k - k2 must be even; 0 ≤ k1 ≤ k2 ≤ k.
    Zero-width slabs come back as empty arrays. Exact by construction — the device
    unpack (`ops.jpeg` stage 2) reproduces src bit-identically."""
    import numpy as np

    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    src = np.ascontiguousarray(src, dtype=np.int16)
    n, nb, k = src.shape
    k1, k2 = int(k1), int(k2)
    if not 0 <= k1 <= k2 <= k:
        raise ValueError("need 0 <= k1 <= k2 <= k, got k1=%d k2=%d k=%d" % (k1, k2, k))
    if k1 % 2 or (k - k2) % 2:
        raise ValueError("k1 and k - k2 must be even, got k1=%d k2=%d k=%d" % (k1, k2, k))
    if not is_zigzag and k != 64:
        raise ValueError("natural-order pack_split needs trailing dim 64, got %d" % k)
    head = np.empty((n, nb, k1 * 3 // 2), dtype=np.uint8)
    mid = np.empty((n, nb, k2 - k1), dtype=np.int8)
    tail = np.empty((n, nb, (k - k2) // 2), dtype=np.uint8)
    rc = lib.ptpu_jpeg_pack_split(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        n * nb, k, 1 if is_zigzag else 0, k1, k2,
        head.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        mid.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        tail.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return (head, mid, tail) if rc == 0 else None


def jpeg_pack_runs_native(runs, tiers, threads=1):
    """Stage-2's shipped input, every component packed in one native pass straight
    from the rows' own coefficients, with no stacked copy in between.

    ``runs``: ``[(srcs, start, count), ...]``, each the rows ``src[start:start +
    count]`` of every component's ``src`` in ``srcs``, an ``(m, nblocks, 64)`` int16
    C-contiguous natural-order array; the output's rows follow the runs in order.
    ``tiers``: per component, None (left alone) or ``(k, split, pack12)``, ``k`` the
    zigzag prefix shipped (64: all of it). A component's result is what
    :func:`jpeg_zigzag_truncate_native` (at k < 64) followed by
    :func:`jpeg_pack_split_native` (``split=(k1, k2)``), :func:`jpeg_pack12_native`
    (``pack12``) or nothing gives for the stacked rows, byte for byte; it is None
    where left alone or on the same range failures. ``threads`` contiguous row
    slices are packed at once (GIL released)."""
    import numpy as np

    lib = _load()
    if lib is None:
        raise RuntimeError("native jpeg decoder unavailable: %s" % _LIB_ERR)
    ncomp = len(tiers)
    if not 1 <= ncomp <= 4 or any(len(srcs) != ncomp for srcs, _s, _c in runs):
        raise ValueError("need 1-4 components, as many in every run as tiers")
    counts = np.array([c for _s, _st, c in runs], dtype=np.int64)
    n = int(counts.sum())
    # row i of a run: that run's first row, plus its place in the run
    place = np.arange(n, dtype=np.uint64) - np.repeat(
        (np.cumsum(counts) - counts).astype(np.uint64), counts)
    rows = np.empty((ncomp, n), dtype=np.uint64)
    nblocks = np.empty(ncomp, dtype=np.int64)
    spec = np.full((ncomp, 4), -1, dtype=np.int32)
    outs, ptrs = [], []
    for c, tier in enumerate(tiers):
        nb = runs[0][0][c].shape[1]
        firsts = []
        for srcs, start, count in runs:
            src = srcs[c]
            if src.dtype != np.int16 or src.ndim != 3 or src.shape[1:] != (nb, 64) \
                    or not src.flags.c_contiguous:
                raise ValueError("each run needs C-contiguous (m, %d, 64) int16 arrays, "
                                 "got %s %s" % (nb, src.dtype, src.shape))
            if not 0 <= start <= start + count <= src.shape[0]:
                raise ValueError("rows [%d, %d) outside a run of %d"
                                 % (start, start + count, src.shape[0]))
            firsts.append(src.ctypes.data + start * nb * 128)
        rows[c] = np.repeat(np.array(firsts, dtype=np.uint64), counts) \
            + place * np.uint64(nb * 128)
        nblocks[c] = nb
        out = None
        if tier is not None:
            k, split, pack12 = int(tier[0]), tier[1], bool(tier[2])
            if not 1 <= k <= 64:
                raise ValueError("k must be in [1, 64], got %r" % (k,))
            if split is not None:
                k1, k2 = int(split[0]), int(split[1])
                if not 0 <= k1 <= k2 <= k or k1 % 2 or (k - k2) % 2:
                    raise ValueError("need 0 <= k1 <= k2 <= k with k1 and k - k2 even, "
                                     "got k1=%d k2=%d k=%d" % (k1, k2, k))
                spec[c] = (k, 2, k1, k2)
                out = (np.empty((n, nb, k1 * 3 // 2), dtype=np.uint8),
                       np.empty((n, nb, k2 - k1), dtype=np.int8),
                       np.empty((n, nb, (k - k2) // 2), dtype=np.uint8))
            elif pack12:
                if k % 2:
                    raise ValueError("pack12 needs an even k, got %d" % k)
                spec[c] = (k, 1, 0, 0)
                out = np.empty((n, nb, k * 3 // 2), dtype=np.uint8)
            else:
                spec[c] = (k, 0, 0, 0)
                out = np.empty((n, nb, k), dtype=np.int16)
        outs.append(out)
        slabs = out if isinstance(out, tuple) else () if out is None else (out,)
        ptrs += [a.ctypes.data for a in slabs] + [None] * (3 - len(slabs))
    status = np.zeros(ncomp, dtype=np.int32)
    lib.ptpu_jpeg_pack_rows(
        rows.ctypes.data, n, ncomp, nblocks.ctypes.data, spec.ctypes.data,
        (ctypes.c_void_p * (3 * ncomp))(*ptrs), status.ctypes.data, int(threads))
    return [None if st else out for out, st in zip(outs, status)]


_PACK_LIB = None
_PACK_ERR = None


def _load_pack():
    """The sequence packer (packing.cpp), a shared object of its own, or None."""
    global _PACK_LIB, _PACK_ERR
    if _PACK_LIB is not None or _PACK_ERR is not None:
        return _PACK_LIB
    with _LOCK:
        if _PACK_LIB is not None or _PACK_ERR is not None:
            return _PACK_LIB
        if os.environ.get("PETASTORM_TPU_DISABLE_NATIVE"):
            _PACK_ERR = "disabled via PETASTORM_TPU_DISABLE_NATIVE"
            return None
        try:
            path = _compile([os.path.join(_SRC_DIR, "packing.cpp")], "ptpu_packing")
            lib = ctypes.CDLL(path)
            lib.ptpu_pack_place.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
            lib.ptpu_pack_place.restype = ctypes.c_int64
            _PACK_LIB = lib
        except Exception as e:  # noqa: BLE001 — degrade to the numpy packer
            _PACK_ERR = str(e)
            logger.warning("Native packer unavailable (%s); using numpy", e)
        return _PACK_LIB


def pack_native_available():
    """True when the native packer loaded (builds it on first call)."""
    return _load_pack() is not None


def pack_place_native(values, src, lens, i, fill, tokens, table, count):
    """First-fit placement of chunks ``[i, len(src))`` into one packed batch, in one
    GIL-free call (``ptpu_pack_place``): ``values`` int32, ``src``/``lens`` int64,
    ``fill`` int32 ``[rows]``, ``tokens`` int32 ``[rows * length]``, ``table`` int32
    ``[rows * length, 3]``, all C-contiguous and updated in place; ``count`` (an
    ``int``) is the table's used length. The caller has checked each chunk's bounds
    (``0 < lens <= length``, ``src + lens <= len(values)``) once for the whole
    delivery. Returns ``(next chunk, new count)``; the next chunk is below
    ``len(src)`` when the batch is full."""
    lib = _load_pack()
    if lib is None:
        raise RuntimeError("native packer unavailable: %s" % _PACK_ERR)
    rows = len(fill)
    length = len(tokens) // rows
    for arr, dt in ((values, "int32"), (src, "int64"), (lens, "int64"), (fill, "int32"),
                    (tokens, "int32"), (table, "int32")):
        if arr.dtype != dt or not arr.flags.c_contiguous:
            raise ValueError("pack_place_native needs C-contiguous %s arrays" % dt)
    if len(src) != len(lens) or table.shape != (rows * length, 3) or not 0 <= i <= len(src):
        raise ValueError("pack_place_native: inconsistent shapes")
    n = ctypes.c_int64(count)
    nxt = lib.ptpu_pack_place(values.ctypes.data, src.ctypes.data, lens.ctypes.data, i,
                              len(src), rows, length, fill.ctypes.data, tokens.ctypes.data,
                              table.ctypes.data, ctypes.byref(n))
    return int(nxt), int(n.value)
