"""Sequence packing: ragged token documents into fixed ``[batch, length]`` rows.

Language-model pretraining reads tokenized documents whose lengths are
heavy-tailed and packs them into fixed-length rows; each row carries segment ids
and positions so that attention does not cross a document boundary. The work is
split so that the host does least:

- the reader keeps a ragged numeric list column columnar (:class:`RaggedColumn`:
  one ``values`` and one ``offsets`` array, no Python object per token);
- :class:`Packer` (host, the loader's producer thread) cuts documents into
  chunks, places them first-fit and copies each chunk's tokens once into its
  slots, in one native call a batch (``packing.cpp``; a numpy twin where the
  native build is unavailable). It also emits a small chunk table;
- only the ``[batch, length]`` tokens and the chunk table cross to the device,
  where ``jit_ptpu_pack_layout`` (:func:`layout_program`) derives the segment
  ids and positions. :func:`layout_numpy` is its host twin.

The rules (``DataLoader(pack=...)`` documents them for users):

- documents are taken in delivered order; an empty document gives no chunk; a
  document of ``n`` tokens gives ``ceil(n / length)`` chunks, in order, each
  ``length`` tokens except the last — no token is dropped;
- each chunk goes to the lowest-index row of the batch being formed that has
  room for it; when no row has room the batch is emitted and the chunk starts the
  next batch, so a part-filled batch carries across deliveries and epochs;
- tokens are 0 in padding; segment ids count a row's chunks 1, 2, ... in
  placement order and are 0 in padding; positions run 0 .. len-1 within each
  chunk and are 0 in padding.
"""
from __future__ import annotations

import collections
import itertools

import numpy as np

#: the chunk table's first bucket: tables are padded to a power of two (at least
#: this) so the device program compiles for a few shapes, not one per batch
TABLE_MIN_BUCKET = 64


class RaggedColumn:
    """A ragged numeric list column kept columnar: row ``i`` is
    ``values[offsets[i]:offsets[i + 1]]`` (``offsets`` int64, absolute indices
    into ``values``, which may be a read-only view of an Arrow buffer and is never
    written). Only a packing loader takes this form (:class:`Packer` reads it as
    it is); :meth:`to_rows` gives the object array of per-row arrays that every
    other consumer of a ragged column gets."""

    __slots__ = ("values", "offsets")

    def __init__(self, values, offsets):
        self.values = values
        self.offsets = np.asarray(offsets, dtype=np.int64)

    def __len__(self):
        return len(self.offsets) - 1

    def to_rows(self):
        """Object array of per-row arrays: writable views of one copy of the values."""
        lo = self.offsets[0]
        values = self.values[lo:self.offsets[-1]].copy()
        out = np.empty(len(self), dtype=object)
        bounds = (self.offsets - lo).tolist()
        for i in range(len(self)):
            out[i] = values[bounds[i]:bounds[i + 1]]
        return out

    def __repr__(self):
        return "RaggedColumn(rows=%d, values=%d, dtype=%s)" % (
            len(self), self.offsets[-1] - self.offsets[0], self.values.dtype)


def ragged_parts(docs):
    """``(values, offsets)`` of a column of documents in any form a reader
    delivers: a :class:`RaggedColumn`, a 2-D array (every row of one length), or an
    object array (or list) of per-row arrays or lists — the last one row at a time."""
    if isinstance(docs, RaggedColumn):
        return docs.values, docs.offsets
    if isinstance(docs, np.ndarray) and docs.dtype != object:
        if docs.ndim != 2:
            raise TypeError("packing needs a list column of token ids; got a %d-D %s "
                            "array" % (docs.ndim, docs.dtype))
        n, k = docs.shape
        return docs.reshape(-1), np.arange(n + 1, dtype=np.int64) * k
    rows = []
    for i, row in enumerate(docs):
        if row is None:
            raise ValueError("packing needs non-null documents; row %d is null" % i)
        rows.append(np.asarray(row))
    lengths = np.fromiter((len(r) for r in rows), np.int64, len(rows))
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    rows = [r for r in rows if len(r)]  # an empty list reads as float64
    if not rows:
        return np.empty(0, np.int32), offsets
    return np.concatenate(rows), offsets


PackedBatch = collections.namedtuple(
    "PackedBatch", "tokens table rows_used chunks split_docs pad_slots")
PackedBatch.__doc__ = """One packed batch from :class:`Packer`: ``tokens``
``[batch, length]`` int32; ``table`` ``[bucket, 3]`` int32 ``(row, start, length)``
per chunk in placement order, padded to a power of two with rows of ``(batch, 0,
0)``; ``rows_used`` (rows holding a chunk: a prefix of the rows); ``chunks``;
``split_docs`` (documents longer than the row whose first chunk is here);
``pad_slots``."""


class _Delivery:
    """One delivery's chunks waiting for placement."""

    __slots__ = ("values", "src", "lens", "split", "pos", "lens_list")

    def __init__(self, values, src, lens, split):
        self.values, self.src, self.lens, self.split = values, src, lens, split
        self.pos = 0
        self.lens_list = None  # the numpy placement's Python view of ``lens``


class Packer:
    """First-fit packer of token documents into ``[batch_size, length]`` batches.

    ``feed(docs)`` queues a delivery's documents (cut into chunks, vectorized);
    ``next_batch()`` places queued chunks and returns the batch once a chunk fits in
    no row (None when the queue runs dry first: the part-filled batch carries on to
    the next ``feed``); ``finish()`` returns the part-filled batch at the end of the
    stream. ``use_native=False`` forces the numpy twin of the native placement."""

    def __init__(self, batch_size, length, use_native=True):
        self.batch_size, self.length = int(batch_size), int(length)
        if self.batch_size <= 0 or self.length <= 0:
            raise ValueError("Packer needs a positive batch size and length")
        self._native = None
        if use_native:
            from petastorm_tpu.ops import native

            if native.pack_native_available():
                self._native = native.pack_place_native
        self._pending = collections.deque()
        self._table = np.zeros((self.batch_size * self.length, 3), np.int32)
        self._open()

    def _open(self):
        self._tokens = np.zeros(self.batch_size * self.length, np.int32)
        self._fill = np.zeros(self.batch_size, np.int32)
        self._count = 0
        self._split = 0

    def feed(self, docs):
        values, offsets = ragged_parts(docs)
        if values.dtype.kind not in "iu":
            if len(values):
                raise TypeError("packing needs integer token ids; got %s" % values.dtype)
            values = values.astype(np.int32)
        if values.dtype != np.int32:
            if len(values) and (values.min() < np.iinfo(np.int32).min
                                or values.max() > np.iinfo(np.int32).max):
                raise ValueError("token ids do not fit int32")
            values = values.astype(np.int32)
        values = np.ascontiguousarray(values)
        lengths = np.diff(offsets)
        if len(lengths) and (lengths.min() < 0 or offsets[0] < 0
                             or offsets[-1] > len(values)):
            raise ValueError("document offsets outside the token values")
        length = self.length
        nch = -(-lengths // length)
        total = int(nch.sum())
        if not total:
            return
        doc = np.repeat(np.arange(len(lengths)), nch)
        k = np.arange(total) - np.repeat(np.cumsum(nch) - nch, nch)
        src = offsets[:-1][doc] + k * length
        lens = np.minimum(lengths[doc] - k * length, length)
        split = (k == 0) & (nch[doc] > 1)
        self._pending.append(_Delivery(values, src, lens, split))

    def next_batch(self):
        while self._pending:
            d = self._pending[0]
            start = d.pos
            if self._native is not None:
                nxt, self._count = self._native(
                    d.values, d.src, d.lens, start, self._fill, self._tokens,
                    self._table, self._count)
            else:
                nxt, self._count = self._place_numpy(d, start)
            self._split += int(d.split[start:nxt].sum())
            d.pos = nxt
            if nxt < len(d.src):
                return self._emit()
            self._pending.popleft()
        return None

    def finish(self):
        """The part-filled batch at the end of the stream (None when it holds no
        chunk)."""
        if self._count == 0:
            return None
        return self._emit()

    def _place_numpy(self, d, i):
        """The native placement's twin: the first-fit loop over chunks, then one
        vectorized gather and scatter of their tokens."""
        rows, length, fill = self.batch_size, self.length, self._fill
        start = i
        free = (length - fill).tolist()
        dst = []
        if d.lens_list is None:
            d.lens_list = d.lens.tolist()
        for c in itertools.islice(d.lens_list, start, None):
            for r in range(rows):
                if free[r] >= c:
                    break
            else:
                break
            dst.append(r * length + length - free[r])
            free[r] -= c
            i += 1
        fill[:] = length - np.asarray(free, np.int32)
        if dst:
            dst = np.asarray(dst, np.int64)
            cl = d.lens[start:i]
            count = self._count + len(dst)
            self._table[self._count:count] = np.stack([dst // length, dst % length, cl], 1)
            rel = np.arange(int(cl.sum())) - np.repeat(np.cumsum(cl) - cl, cl)
            self._tokens[np.repeat(dst, cl) + rel] = d.values[np.repeat(d.src[start:i], cl)
                                                              + rel]
            return i, count
        return i, self._count

    def _emit(self):
        rows, length, count = self.batch_size, self.length, self._count
        bucket = min(rows * length, max(TABLE_MIN_BUCKET, 1 << (count - 1).bit_length()))
        table = np.zeros((bucket, 3), np.int32)
        table[:count] = self._table[:count]
        table[count:, 0] = rows
        used = int(np.count_nonzero(self._fill))
        out = PackedBatch(self._tokens.reshape(rows, length), table, used, count,
                          self._split, rows * length - int(self._fill.sum()))
        self._open()
        return out


def layout_numpy(table, batch, length):
    """Segment ids and positions ``[batch, length]`` int32 of a chunk table, on the
    host: the twin of :func:`layout_program`. Table rows at ``row >= batch`` are
    padding."""
    table = np.asarray(table)
    real = table[:, 0] < batch
    rows, starts, lens = (table[real, j].astype(np.int64) for j in range(3))
    marks = np.zeros(batch * length, np.int32)
    marks[rows * length + starts] = 1
    marks = marks.reshape(batch, length)
    fill = np.bincount(rows, weights=lens, minlength=batch)[:batch]
    t = np.arange(length)
    valid = t[None, :] < fill[:, None]
    seg = np.where(valid, np.cumsum(marks, axis=1), 0).astype(np.int32)
    last = np.maximum.accumulate(np.where(marks > 0, t[None, :], 0), axis=1)
    pos = np.where(valid, t[None, :] - last, 0).astype(np.int32)
    return seg, pos


def layout_program(batch, length, out_sharding=None):
    """``jit_ptpu_pack_layout``: ``table -> (segment_ids, positions)`` on the device,
    each ``[batch, length]`` int32, laid out by ``out_sharding`` when given. Reads
    the table (12 bytes a chunk) and writes 8 bytes a slot."""
    import jax
    import jax.numpy as jnp

    def ptpu_pack_layout(table):
        rows, starts, lens = table[:, 0], table[:, 1], table[:, 2]
        # padding rows (row == batch) land past the end and are dropped
        marks = jnp.zeros(batch * length, jnp.int32).at[rows * length + starts].set(
            1, mode="drop").reshape(batch, length)
        fill = jnp.zeros(batch + 1, jnp.int32).at[rows].add(lens, mode="drop")[:batch]
        t = jax.lax.broadcasted_iota(jnp.int32, (batch, length), 1)
        valid = t < fill[:, None]
        seg = jnp.where(valid, jnp.cumsum(marks, axis=1), 0)
        last = jax.lax.cummax(jnp.where(marks > 0, t, 0), axis=1)
        pos = jnp.where(valid, t - last, 0)
        return seg, pos

    if out_sharding is None:
        return jax.jit(ptpu_pack_layout)
    return jax.jit(ptpu_pack_layout, out_shardings=(out_sharding, out_sharding))
