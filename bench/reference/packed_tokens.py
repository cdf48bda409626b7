"""Plain reference for the packed token stream: pyarrow reads the stored documents,
numpy cuts them into chunks and keys them, and a straightforward loop packs them. It
imports nothing of the program.

A delivered batch is ``input_ids``, ``input_ids_segment_ids`` and
``input_ids_positions``, each ``[rows, length]``. The benchmark's own
``bench_ident_packed`` program keeps, of every batch, a 64-bit key for each of its
first ``segment_slots`` segments in row-major order (a position-weighted hash of the
segment's tokens, the same function numpy applies to the store's chunks), each row's
segment count, the batch's layout errors and its padding slots: 8 KB a batch at
1,024 slots. Each batch holds a contiguous stretch of
the stream of chunks (a chunk that fits in no row ends the batch and starts the next),
so the stream is rebuilt batch by batch from where each key lies in the store.
Compared:

- ``segments_unmatched``: delivered segments whose key is no chunk of the store (a
  batch with more segments than the slots counts its overflow here too);
- ``segments_out_of_run`` and ``epoch_spread``: ``runs.py`` on the stream of chunk
  keys (row groups in a seeded order each epoch, chunks in store order);
- ``epoch_lead_excess``: over the same runs, how far row groups run ahead of the
  least-delivered one beyond the slack the pool's reordering takes
  (:func:`lead_excess`);
- ``layout_errors``: segment ids not 1..k then 0, positions not 0..len-1 within a
  segment, nonzero tokens in padding;
- ``placement_mismatched``: sampled batches not bit-equal (tokens, segment ids,
  positions) to the loop packer fed the same stretch of chunks;
- ``pad_slot_share`` (read, not compared): padding slots over all slots.
"""
import os

import numpy as np

from reference import runs

_K = [np.uint32(k) for k in (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
                             0x165667B1, 0xD3A2646C, 0xFD7046C5, 0xB55A4F09)]


def token_hash(tok, pos, xp=np):
    """Two uint32 lanes of each token's hash at its position in its chunk."""
    t, p = tok.astype(xp.uint32), pos.astype(xp.uint32)
    lanes = []
    for a, b, c in ((_K[0], _K[1], _K[2]), (_K[3], _K[4], _K[5])):
        h = t * a + p * b + c
        h = h ^ (h >> xp.uint32(15))
        h = h * _K[6]
        h = h ^ (h >> xp.uint32(13))
        lanes.append(h * _K[7])
    return lanes


def as_key(lanes):
    """(..., 2) uint32 lanes as uint64 keys."""
    lanes = np.asarray(lanes).astype(np.uint64)
    return (lanes[..., 0] << np.uint64(32)) | lanes[..., 1]


#: epochs a row group may lead the least-delivered row group before
#: ``epoch_lead_excess`` counts: the pool's per-worker claims and results queue
#: hold about 32 row groups in flight (four and a half epochs of seven), and the
#: order in which they come out lets a sound stream lead by up to about three
EPOCH_SLACK = 3


def lead_excess(occurrences, n_groups, slack=EPOCH_SLACK):
    """The sum, over the delivered row-group runs, of the epochs by which the run's
    row group leads the least-delivered row group beyond ``slack``. A row group
    leads by ``k`` when it has been delivered ``k + 1`` times more than the one
    delivered least. Each row group once an epoch, in any order the pool's
    reordering gives, keeps it at 0; a row group delivered twice in an epoch, or
    one skipped, leaves a lead that the later epochs never take back, so every
    later delivery adds to it."""
    counts = np.zeros(n_groups, np.int64)
    total = 0
    for g in occurrences:
        counts[g] += 1
        total += max(0, int(counts[g] - counts.min()) - 1 - slack)
    return total


def chunks_of(lengths, length):
    """``(start, length)`` of every chunk of documents of ``lengths`` tokens laid end
    to end, in order."""
    nch = -(-lengths // length)
    doc = np.repeat(np.arange(len(lengths)), nch)
    k = np.arange(int(nch.sum())) - np.repeat(np.cumsum(nch) - nch, nch)
    starts = np.concatenate([[0], np.cumsum(lengths)])[:-1][doc] + k * length
    return starts, np.minimum(lengths[doc] - k * length, length)


def chunk_keys(values, starts, lens):
    """uint64 key of every chunk ``values[start:start + len]``; the chunks tile
    ``values`` in order."""
    if not len(starts):
        return np.empty(0, np.uint64)
    lanes = token_hash(values, np.arange(len(values)) - np.repeat(starts, lens))
    return as_key(np.stack([np.add.reduceat(h, starts, dtype=np.uint32) for h in lanes],
                           axis=1))


def loop_pack(chunks, batch, length):
    """The packing rules in a plain loop over chunks: tokens, segment ids and
    positions of one batch that starts empty."""
    tok, seg, pos = (np.zeros((batch, length), np.int32) for _ in range(3))
    fill = [0] * batch
    count = [0] * batch
    for chunk in chunks:
        for r in range(batch):
            if fill[r] + len(chunk) <= length:
                break
        else:
            raise ValueError("a chunk fits in no row of the batch")
        a, b = fill[r], fill[r] + len(chunk)
        count[r] += 1
        tok[r, a:b] = chunk
        seg[r, a:b] = count[r]
        pos[r, a:b] = np.arange(len(chunk))
        fill[r] = b
    return tok, seg, pos


def head_order(loc, row):
    """The row groups of ``loc`` (``(group, index)`` of a batch's chunks that do
    not continue the previous batch, row by row in placement order) in stream
    order: a group whose chunk comes first in some row comes first; otherwise by
    first chunk, then by number."""
    first = {}
    for g, i in loc.tolist():
        first[g] = min(first.get(g, i), i)
    after = {g: set() for g in first}
    for r in np.unique(row):
        seq = loc[row == r, 0].tolist()
        for a, b in zip(seq, seq[1:]):
            if a != b:
                after[b].add(a)
    order, left = [], sorted(first, key=lambda g: (first[g], g))
    while left:
        ready = [g for g in left if not after[g] - set(order)] or left
        order.append(ready[0])
        left.remove(ready[0])
    return order


class Reference:
    def __init__(self, cfg):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.field = cfg["field"]
        self.slots = cfg["segment_slots"]
        self.batches = 0
        slots = self.slots

        @jax.jit
        def bench_ident_packed(tok, seg, pos):
            # segment k's key is the difference of running sums of the token hashes
            # at the last slot of segments k and k - 1 (row-major): no scatter
            live = seg > 0
            count = jnp.max(seg, axis=1)
            before = (jnp.cumsum(count) - count)[:, None]
            run = jax.lax.cummax(jnp.where(live, before + seg - 1, -1).reshape(-1))
            ends = jnp.searchsorted(run, jnp.arange(slots), side="right") - 1
            keys = []
            for h in token_hash(tok, pos, jnp):
                total = jnp.cumsum(jnp.where(live, h, 0).reshape(-1))[ends]
                keys.append(total - jnp.pad(total[:-1], (1, 0)))
            prev_seg = jnp.pad(seg[:, :-1], ((0, 0), (1, 0)))
            prev_pos = jnp.pad(pos[:, :-1], ((0, 0), (1, 0)), constant_values=-1)
            new = live & (seg != prev_seg)
            bad = jnp.where(live,
                            jnp.where(new, (seg != prev_seg + 1) | (pos != 0),
                                      pos != prev_pos + 1),
                            (pos != 0) | (tok != 0))
            # padding only after a row's segments
            tail = (seg[:, :-1] == 0) & (seg[:, 1:] != 0)
            return {"keys": jnp.stack(keys, axis=1), "count": count,
                    "errors": jnp.sum(bad) + jnp.sum(tail),
                    "pad": jnp.sum(~live)}

        self._ident = bench_ident_packed

    def identity(self, batch):
        f = self.field
        self.batches += 1
        return self._ident(batch[f], batch[f + "_segment_ids"], batch[f + "_positions"])

    def sample(self, batch):
        f = self.field
        return {"batch": self.batches - 1, "tok": batch[f],
                "seg": batch[f + "_segment_ids"], "pos": batch[f + "_positions"]}

    def check(self, store, idents, samples):
        import pyarrow.parquet as pq

        length = self.cfg["length"]
        pf = pq.ParquetFile(os.path.join(store, "part-00000.parquet"))
        groups, docs = [], []
        for g in range(pf.num_row_groups):
            col = pf.read_row_group(g, columns=[self.field]).column(self.field)
            arr = col.combine_chunks()
            values = arr.values.to_numpy()
            lens = np.diff(arr.offsets.to_numpy()).astype(np.int64)
            starts, clens = chunks_of(lens, length)
            base = arr.offsets.to_numpy()[0]
            values = values[base:base + int(lens.sum())]
            groups.append(chunk_keys(values, starts, clens))
            docs.append((values, starts, clens))
        keys = np.concatenate(groups)
        where = np.concatenate([np.stack([np.full(len(k), g), np.arange(len(k))], 1)
                                for g, k in enumerate(groups)])
        order = np.argsort(keys, kind="stable")

        def locate(want):
            k = np.minimum(np.searchsorted(keys, want, sorter=order), len(keys) - 1)
            pos = order[k]
            return where[pos], keys[pos] == want

        stream, spans = [], []
        unmatched = errors = pad = slots_total = 0
        prev = (-1, -1)
        for ident in idents:
            count = np.asarray(ident["count"])
            rows = len(count)
            held = min(int(count.sum()), self.slots)
            unmatched += int(count.sum()) - held
            got = as_key(np.asarray(ident["keys"])[:held])
            row = np.repeat(np.arange(rows), count)[:held]
            loc, ok = locate(got)
            unmatched += int(np.sum(~ok))
            got, loc, row = got[ok], loc[ok], row[ok]
            # the stretch of the stream this batch holds: first the chunks that
            # continue the previous batch's row group, then row groups from their
            # first chunk on, in the order the rows place them
            cont = (loc[:, 0] == prev[0]) & (loc[:, 1] > prev[1])
            rank_of = {g: j + 1 for j, g in enumerate(
                head_order(loc[~cont], row[~cont]))}
            rank = np.array([0 if c else rank_of[g]
                             for c, g in zip(cont.tolist(), loc[:, 0].tolist())], np.int64)
            seq = np.lexsort((loc[:, 1], rank))
            spans.append((len(stream), len(stream) + len(seq), loc[seq]))
            stream.extend(got[seq].tolist())
            if len(seq):
                prev = tuple(loc[seq[-1]])
            errors += int(ident["errors"])
            pad += int(ident["pad"])
            slots_total += rows * length
        stream = np.array(stream, np.uint64)
        occ, out_of_run = runs.parse_runs(stream, groups)

        mismatched = 0
        for s in samples:
            _a, _b, loc = spans[s["batch"]]
            chunks = [docs[g][0][docs[g][1][i]:docs[g][1][i] + docs[g][2][i]]
                      for g, i in loc]
            tok = np.asarray(s["tok"])
            try:
                want = loop_pack(chunks, len(tok), length)
            except ValueError:
                mismatched += 1
                continue
            got = (tok, np.asarray(s["seg"]), np.asarray(s["pos"]))
            mismatched += int(not all(np.array_equal(a, b) for a, b in zip(got, want)))
        numbers = {
            "segments_unmatched": unmatched,
            "segments_out_of_run": out_of_run,
            "epoch_spread": runs.epoch_spread(occ, len(groups)),
            "epoch_lead_excess": lead_excess(occ, len(groups)),
            "layout_errors": errors,
            "placement_mismatched": mismatched,
            "pad_slot_share": pad / max(slots_total, 1),
        }
        return {"rows": slots_total // length,
                "failed": unmatched + out_of_run + errors + mismatched,
                "numbers": numbers,
                "info": {"row_groups_delivered": len(occ), "segments": len(stream),
                         "batches_sampled": len(samples)}}
