"""Pinned-host staging pool for the transfer thread's ``device_put`` (ISSUE 6).

``jax.device_put`` from an arbitrary numpy array stages the H2D transfer from
pageable memory: the runtime either pins pages on the fly or bounces through
an internal staging buffer — per batch, on the hot transfer thread. This pool
keeps a small ring of page-locked (``mlock``) host slabs; the transfer thread
copies each batch's device-bound columns into a leased slab ONCE (the copy
the census charges to ``h2d_stage``) and launches ``device_put`` from
page-locked memory, so the DMA engine reads directly with no runtime-side
bounce. The slab's :class:`petastorm_tpu.io.lease.Lease` returns it to the
ring; the loader releases it after the transfer completes
(``jax.block_until_ready``), so a slab is never rewritten under an in-flight
DMA.

Degradations (never failures):

- ``mlock`` refused (``RLIMIT_MEMLOCK``, platform): slabs stay pageable but
  pooled — the allocator churn still disappears
  (``ptpu_degradations_total{cause="staging_unpinned"}``, warn-once).
- batch larger than a slab, or the ring starved: that batch stages the old
  way, straight from its own buffers (``staging_oversized`` — watch it grow
  and raise ``slab_bytes``).

The pool is only correct on backends whose ``device_put`` COPIES host memory
(TPU/GPU H2D — the target). The CPU backend zero-copy-aliases aligned numpy
arrays (see :func:`device_put_aliases_host`), which would hand consumers
arrays aliasing a recycled slab; the loader probes once and refuses/degrades
there.
"""
from __future__ import annotations

import ctypes
import mmap
import queue
import threading

import numpy as np

from petastorm_tpu.io.lease import Lease, count_copy
from petastorm_tpu.obs.log import degradation

#: per-array offsets inside a staging slab are rounded up to this (page-ish
#: alignment keeps each column's DMA descriptor friendly)
_STAGE_ALIGN = 256

_alias_probe_lock = threading.Lock()
_alias_probe = None


def device_put_aliases_host():
    """True when this process's default jax backend ALIASES host numpy memory
    in ``device_put`` (the CPU backend's zero-copy path) instead of copying.
    Probed once: transfer a small array, mutate the source, read the device
    value back. On aliasing backends staged-slab reuse (and slab-lease release
    after transfer) would corrupt delivered batches, so callers must hand
    ``device_put`` owned buffers there."""
    global _alias_probe
    if _alias_probe is None:
        with _alias_probe_lock:
            if _alias_probe is None:
                try:
                    import jax

                    probe = np.arange(64, dtype=np.float32)
                    dev = jax.device_put(probe)
                    # device_put is async: a copying backend may not have read
                    # the source yet — mutating it now would race the H2D copy
                    # and misclassify the backend as aliasing
                    jax.block_until_ready(dev)
                    probe[0] = -1.0
                    _alias_probe = bool(np.asarray(dev)[0] == -1.0)
                except Exception:  # noqa: BLE001 — no jax / no device: nothing
                    _alias_probe = False  # will ever alias
    return _alias_probe


def owns_memory(arr):
    """True when numpy allocated the memory ``arr`` reads (``arr``, or the array
    it views, owns its data). No ring, arena or staging pool recycles such
    memory, and ``device_put`` keeps a reference to it until its transfer ends.
    A view into a slab, a shared segment or any other foreign buffer is
    borrowed."""
    while isinstance(arr.base, np.ndarray):
        arr = arr.base
    return arr.flags.owndata


#: per thread, the list :func:`put_program_inputs` adds the device arrays of
#: borrowed inputs to while :func:`watch_borrowed_inputs` is open
_borrowed = threading.local()


class watch_borrowed_inputs:
    """While open on this thread, :func:`put_program_inputs` minds inputs put
    from borrowed memory; the ``as`` target is the list it adds their device
    arrays to, for the caller to wait on before the borrowed buffers go back to
    their owners. ``active=False`` (nothing borrowed can be recycled) watches
    nothing and gives an empty tuple."""

    __slots__ = ("held",)

    def __init__(self, active=True):
        self.held = [] if active else None

    def __enter__(self):
        _borrowed.held = self.held
        return self.held if self.held is not None else ()

    def __exit__(self, *exc):
        _borrowed.held = None
        return False


def put_program_inputs(tree, put):
    """``put(tree)``, a ``device_put`` of a device program's host inputs, minding
    borrowed memory while :func:`watch_borrowed_inputs` is open on this thread.
    An input numpy did not allocate (:func:`owns_memory`) is copied owned first
    where ``device_put`` aliases host memory (its device array would otherwise
    be the recycled buffer); elsewhere its device array joins the watch. The
    program's outputs never need a wait: its inputs are then owned or waited
    on."""
    held = getattr(_borrowed, "held", None)
    if held is None:
        return put(tree)
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    borrowed = [isinstance(a, np.ndarray) and not owns_memory(a) for a in leaves]
    if not any(borrowed):
        return put(tree)
    if device_put_aliases_host():
        count_copy("h2d_owned_copy", sum(a.nbytes for a, b in zip(leaves, borrowed)
                                         if b))
        return put(jax.tree_util.tree_unflatten(
            treedef, [a.copy() if b else a for a, b in zip(leaves, borrowed)]))
    out = put(tree)
    held.extend(d for d, b in zip(jax.tree_util.tree_leaves(out), borrowed) if b)
    return out


def _try_mlock(buf, nbytes):
    """Page-lock ``buf`` via libc ``mlock``; False (with a warn-once
    degradation) when the platform or RLIMIT_MEMLOCK refuses."""
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        if libc.mlock(ctypes.c_void_p(addr), ctypes.c_size_t(nbytes)) == 0:
            return True
        err = ctypes.get_errno()
    except Exception as e:  # noqa: BLE001 — exotic libc/platform
        err = e
    degradation(
        "staging_unpinned",
        "mlock of a %d-byte staging slab refused (%s); H2D staging slabs are "
        "pooled but PAGEABLE — raise RLIMIT_MEMLOCK to pin them", nbytes, err)
    return False


class StagedSlab(Lease):
    """The lease over one staged batch's slab, which also says where the batch
    sits in it: ``prefix`` is the slab's used bytes ``[0, end)`` as a read-only
    ``uint8`` array (a view, no copy), and ``columns`` holds ``(name, offset,
    shape, dtype)`` for each staged column, in staging order. Offsets round up
    to ``_STAGE_ALIGN``; the bytes of a gap belong to no column."""

    __slots__ = ("prefix", "columns")

    def __init__(self, prefix, columns, release_cb):
        super().__init__(release_cb=release_cb, kind="staging_slab")
        self.prefix = prefix
        self.columns = columns


class PinnedStagingPool:
    """Ring of page-locked host slabs the transfer thread stages device-bound
    batches into before ``device_put``.

    ``stage(arrays)`` returns ``(staged_arrays, lease)``: the staged dict maps
    the same keys to ndarray views INTO one slab (read-only — nothing may
    write a slab under DMA), and the lease (a :class:`StagedSlab`, which also
    gives each column's place in the slab) returns the slab to the ring on
    release. Returns ``(arrays, None)`` unchanged when the batch cannot stage
    (oversized / ring starved) — callers need no special path.
    """

    def __init__(self, slab_bytes, num_slabs=3, acquire_timeout_s=2.0):
        if slab_bytes <= 0 or num_slabs <= 0:
            raise ValueError("slab_bytes and num_slabs must be positive")
        self.slab_bytes = int(slab_bytes)
        self._timeout = acquire_timeout_s
        self._slabs = []
        self._closed = False
        self.pinned = True
        for _ in range(num_slabs):
            buf = mmap.mmap(-1, self.slab_bytes)  # anonymous, page-aligned
            self._slabs.append(buf)
            if self.pinned and not _try_mlock(buf, self.slab_bytes):
                self.pinned = False  # degradation logged once; slabs stay pooled
        self._free = queue.Queue()
        for i in range(num_slabs):
            self._free.put(i)

    def __len__(self):
        return len(self._slabs)

    def stage(self, arrays):
        """Copy every ndarray in ``arrays`` into one leased slab; returns
        ``(staged_views, lease)`` or ``(arrays, None)`` on fallback."""
        items = [(k, v) for k, v in arrays.items() if isinstance(v, np.ndarray)]
        end = 0
        spans = []
        for _k, v in items:
            start = -(-end // _STAGE_ALIGN) * _STAGE_ALIGN
            end = start + v.nbytes
            spans.append(start)
        if end > self.slab_bytes:
            degradation(
                "staging_oversized",
                "batch of %d device-bound bytes exceeds the %d-byte staging "
                "slab; transferring from pageable memory (raise slab_bytes)",
                end, self.slab_bytes)
            return arrays, None
        if self._closed:
            return arrays, None
        try:
            slab_id = self._free.get(timeout=self._timeout)
        except queue.Empty:
            degradation(
                "staging_starved",
                "no free H2D staging slab within %.1fs (a transfer is not "
                "completing, or the ring is undersized); transferring from "
                "pageable memory", self._timeout)
            return arrays, None
        buf = memoryview(self._slabs[slab_id])
        prefix = np.frombuffer(buf, dtype=np.uint8, count=end)
        staged = dict(arrays)
        columns = []
        total = 0
        for (name, v), start in zip(items, spans):
            dst = prefix[start:start + v.nbytes].view(v.dtype).reshape(v.shape)
            np.copyto(dst, v)
            dst.flags.writeable = False  # nothing may write a slab under DMA
            staged[name] = dst
            columns.append((name, start, v.shape, v.dtype))
            total += v.nbytes
        prefix.flags.writeable = False
        count_copy("h2d_stage", total)
        lease = StagedSlab(prefix, tuple(columns),
                           release_cb=lambda: self._release(slab_id))
        return staged, lease

    def _release(self, slab_id):
        if not self._closed:
            self._free.put(slab_id)

    def close(self):
        """Unlock + unmap the slabs (idempotent). Outstanding views keep their
        mapping alive until they die (``BufferError`` guard, like the shm
        ring's close)."""
        self._closed = True
        slabs, self._slabs = self._slabs, []
        for buf in slabs:
            try:
                buf.close()
            except BufferError:
                pass  # exported staged views still alive: frees with them
            except Exception:  # noqa: BLE001 — exit path
                pass  # graftlint: disable=GL-O002 (exit path: munmap best-effort)
