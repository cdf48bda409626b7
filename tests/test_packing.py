"""Sequence packing (``DataLoader(pack=...)``, ``petastorm_tpu.ops.packing``): the
packer against a plain reference loop, carry across deliveries and epochs,
exactly-once delivery through the reader, the ``last_batch`` policies, the device
layout program against its numpy twin, a dp=4 mesh, the three pool types, and the
option combinations that raise."""
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from petastorm_tpu.loader import DataLoader
from petastorm_tpu.ops.packing import (Packer, RaggedColumn, layout_numpy,
                                       layout_program)
from petastorm_tpu.reader import make_batch_reader

B, L = 4, 64


def reference_pack(stream, batch, length):
    """The rules in a plain loop: every document's chunks in order, each to the
    lowest-index row with room, the batch emitted when none has room. Returns the
    emitted batches and the part-filled one, each a list of rows of chunks."""
    emitted = []
    rows = [[] for _ in range(batch)]
    for docs in stream:
        for doc in docs:
            doc = [int(t) for t in doc]
            for s in range(0, len(doc), length):
                chunk = doc[s:s + length]
                for r in range(batch):
                    if sum(len(c) for c in rows[r]) + len(chunk) <= length:
                        break
                else:
                    emitted.append(rows)
                    rows = [[] for _ in range(batch)]
                    r = 0
                rows[r].append(chunk)
    return emitted, rows


def render(rows, length):
    """Tokens, segment ids and positions of one reference batch, row by row."""
    shape = (len(rows), length)
    tok, seg, pos = (np.zeros(shape, np.int32) for _ in range(3))
    for r, chunks in enumerate(rows):
        o = 0
        for j, chunk in enumerate(chunks):
            for k, t in enumerate(chunk):
                tok[r, o] = t
                seg[r, o] = j + 1
                pos[r, o] = k
                o += 1
    return tok, seg, pos


def random_stream(seed, deliveries=6):
    """Deliveries of documents 0-200 tokens long (empty ones, exact fits of L and
    splits all occur), ids 1-999."""
    rng = np.random.default_rng(seed)
    stream = []
    for _ in range(deliveries):
        lens = rng.integers(0, 201, rng.integers(0, 25))
        lens[rng.random(len(lens)) < 0.1] = L
        stream.append([rng.integers(1, 1000, int(n)).astype(np.int32) for n in lens])
    return stream


def as_column(docs, form):
    if form == "ragged":
        values = np.concatenate(docs) if docs else np.empty(0, np.int32)
        offsets = np.concatenate([[0], np.cumsum([len(d) for d in docs])])
        return RaggedColumn(values, offsets)
    col = np.empty(len(docs), dtype=object)
    col[:] = [d.tolist() for d in docs] if form == "lists" else docs
    return col


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("seed", range(16))
def test_packer_matches_reference_loop(seed, native):
    stream = random_stream(seed)
    packer = Packer(B, L, use_native=native)
    got = []
    for i, docs in enumerate(stream):
        packer.feed(as_column(docs, ("ragged", "rows", "lists")[(seed + i) % 3]))
        while (batch := packer.next_batch()) is not None:
            got.append(batch)
    tail = packer.finish()
    want, want_tail = reference_pack(stream, B, L)
    assert len(got) == len(want)
    for batch, rows in zip(got + [tail], want + [want_tail]):
        if batch is None:
            assert not any(rows)
            continue
        tok, seg, pos = render(rows, L)
        np.testing.assert_array_equal(batch.tokens, tok)
        got_seg, got_pos = layout_numpy(batch.table, B, L)
        np.testing.assert_array_equal(got_seg, seg)
        np.testing.assert_array_equal(got_pos, pos)
        assert batch.chunks == sum(len(r) for r in rows)
        assert batch.pad_slots == B * L - sum(len(c) for r in rows for c in r)
        assert batch.rows_used == sum(1 for r in rows if r)
        bucket = batch.table.shape[0]
        assert bucket >= 64 and bucket & (bucket - 1) == 0  # a power of two


def test_layout_program_matches_numpy():
    packer = Packer(B, L)
    for docs in random_stream(3, deliveries=12):
        packer.feed(as_column(docs, "ragged"))
    tables = []
    while (batch := packer.next_batch()) is not None:
        tables.append(batch.table)
    tables.append(packer.finish().table)
    fn = layout_program(B, L)
    for table in tables:
        seg, pos = fn(table)
        want_seg, want_pos = layout_numpy(table, B, L)
        np.testing.assert_array_equal(np.asarray(seg), want_seg)
        np.testing.assert_array_equal(np.asarray(pos), want_pos)


def _write_store(path, seed=0, docs=300, row_group_size=37):
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 201, docs)
    lens[::17] = L
    rows = [rng.integers(1, 32000, int(n)).astype(np.int32) for n in lens]
    table = pa.table({"input_ids": pa.array(rows, pa.list_(pa.int32()))})
    path.mkdir()
    pq.write_table(table, str(path / "part-0.parquet"), row_group_size=row_group_size)
    groups = [rows[i:i + row_group_size] for i in range(0, docs, row_group_size)]
    return "file://" + str(path), groups


def _packed(url, batch_size=B, last_batch="partial", to_device=False, sharding=None,
            **reader_kw):
    reader_kw.setdefault("shuffle_row_groups", False)
    reader = make_batch_reader(url, **reader_kw)
    with DataLoader(reader, batch_size, last_batch=last_batch, to_device=to_device,
                    sharding=sharding, pack={"field": "input_ids", "length": L}) as loader:
        out = [{k: np.asarray(v) for k, v in b.items()} for b in loader]
        stats = loader.stats.snapshot()
    return out, stats


def _chunks_of(batches):
    """Every delivered chunk's tokens, in stream order of batches and placement."""
    out = []
    for b in batches:
        tok, seg = b["input_ids"], b["input_ids_segment_ids"]
        for r in range(len(tok)):
            for j in range(1, int(seg[r].max()) + 1):
                out.append(tuple(tok[r][seg[r] == j].tolist()))
    return out


def test_carry_across_deliveries_row_groups_and_epochs(tmp_path):
    url, groups = _write_store(tmp_path / "s")
    got, stats = _packed(url, num_epochs=2, reader_pool_type="dummy")
    want, tail = reference_pack(groups * 2, B, L)
    assert len(got) == len(want) + 1  # partial: the part-filled batch comes last
    for b, rows in zip(got, want + [[r for r in tail if r]]):
        tok, seg, pos = render(rows, L)
        np.testing.assert_array_equal(b["input_ids"], tok)
        np.testing.assert_array_equal(b["input_ids_segment_ids"], seg)
        np.testing.assert_array_equal(b["input_ids_positions"], pos)
    assert stats["pack_chunks"] == sum(len(c) for rows in want + [tail] for c in rows)
    assert stats["pack_slots"] == sum(b["input_ids"].size for b in got)
    assert stats["pack_pad_slots"] == int(sum((b["input_ids_segment_ids"] == 0).sum()
                                              for b in got))
    assert stats["pack_s"] > 0 and stats["rows"] == sum(len(b["input_ids"]) for b in got)


def test_exactly_once_over_an_epoch(tmp_path):
    url, groups = _write_store(tmp_path / "s", seed=1)
    got, _ = _packed(url, num_epochs=1, reader_pool_type="thread", workers_count=3,
                     shuffle_row_groups=True, seed=5)
    want = [tuple(doc[s:s + L].tolist()) for docs in groups for doc in docs
            for s in range(0, len(doc), L)]
    assert sorted(_chunks_of(got)) == sorted(want)
    # a document longer than the row is split, never truncated
    assert sum(len(c) for c in _chunks_of(got)) == sum(len(d) for g in groups for d in g)


@pytest.mark.parametrize("policy", ["drop", "pad", "partial"])
def test_last_batch_policies(tmp_path, policy):
    url, groups = _write_store(tmp_path / "s", seed=3, docs=45)
    want, tail = reference_pack(groups, B, L)
    used = sum(1 for r in tail if r)
    assert 0 < used < B  # the stream ends on a short batch
    got, _ = _packed(url, last_batch=policy, reader_pool_type="dummy")
    full = len(want)
    assert all(len(b["input_ids"]) == B for b in got[:full])
    if policy == "drop":
        assert len(got) == full
        return
    assert len(got) == full + 1
    last = got[-1]
    tok, seg, pos = render([r for r in tail if r], L)
    if policy == "partial":
        assert last["input_ids"].shape == (used, L) and "__valid__" not in last
    else:
        assert last["input_ids"].shape == (B, L)
        np.testing.assert_array_equal(last["__valid__"], np.arange(B) < used)
        assert all(b["__valid__"].all() for b in got[:full])
        assert not last["input_ids"][used:].any()
        assert not last["input_ids_segment_ids"][used:].any()
    np.testing.assert_array_equal(last["input_ids"][:used], tok)
    np.testing.assert_array_equal(last["input_ids_segment_ids"][:used], seg)
    np.testing.assert_array_equal(last["input_ids_positions"][:used], pos)


def test_device_batches_under_dp4_mesh(tmp_path):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    url, _ = _write_store(tmp_path / "s", seed=3)
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    host, _ = _packed(url, batch_size=8, last_batch="drop", reader_pool_type="dummy")
    reader = make_batch_reader(url, shuffle_row_groups=False, reader_pool_type="dummy")
    with DataLoader(reader, 8, sharding=sharding, last_batch="drop",
                    pack={"field": "input_ids", "length": L}) as loader:
        dev = list(loader)
        stats = loader.stats.snapshot()
    assert len(dev) == len(host) > 0
    for d, h in zip(dev, host):
        for k in ("input_ids", "input_ids_segment_ids", "input_ids_positions"):
            assert isinstance(d[k], jax.Array) and d[k].shape == (8, L)
            assert d[k].sharding.is_equivalent_to(sharding, 2)
            np.testing.assert_array_equal(np.asarray(d[k]), h[k])
    # the wire carries the tokens and the chunk table, not three [B, L] arrays
    assert stats["h2d_bytes"] < 2 * len(dev) * 8 * L * 4


def test_pools_deliver_the_same_stream(tmp_path):
    url, _ = _write_store(tmp_path / "s", seed=4)
    runs = {pool: _packed(url, num_epochs=1, reader_pool_type=pool, workers_count=1)[0]
            for pool in ("thread", "dummy", "process")}
    for pool in ("dummy", "process"):
        assert len(runs[pool]) == len(runs["thread"])
        for a, b in zip(runs[pool], runs["thread"]):
            for k in b:
                np.testing.assert_array_equal(a[k], b[k])


def test_reader_delivers_ragged_columns_columnar(tmp_path):
    url, groups = _write_store(tmp_path / "s", seed=5)
    with make_batch_reader(url, shuffle_row_groups=False, reader_pool_type="dummy",
                           num_epochs=1) as reader:
        reader.keep_ragged = True
        got = [b.input_ids for b in reader]
    assert len(got) == len(groups)
    for col, docs in zip(got, groups):
        assert isinstance(col, RaggedColumn)
        np.testing.assert_array_equal(np.diff(col.offsets), [len(d) for d in docs])
        np.testing.assert_array_equal(col.values[col.offsets[0]:col.offsets[-1]],
                                      np.concatenate(docs))
    # without it (the default) the same column is an object array of row arrays
    with make_batch_reader(url, shuffle_row_groups=False, reader_pool_type="dummy",
                           num_epochs=1) as reader:
        first = next(iter(reader)).input_ids
    assert first.dtype == object and first[0].flags.writeable
    np.testing.assert_array_equal(first[1], groups[0][1])


def test_ragged_column_to_rows():
    """Consumers that do not pack get per-row arrays: writable views of one copy of
    the values, empty rows included, the read-only source left alone."""
    values = np.arange(10, dtype=np.int32)
    values.flags.writeable = False
    col = RaggedColumn(values, [0, 3, 3, 7, 10])
    assert len(col) == 4
    rows = col.to_rows()
    assert rows.dtype == object
    assert [r.tolist() for r in rows] == [[0, 1, 2], [], [3, 4, 5, 6], [7, 8, 9]]
    assert all(r.flags.writeable for r in rows)
    assert rows[0].base is rows[2].base and rows[0].base is not values
    tail = RaggedColumn(values, [7, 10]).to_rows()
    assert [r.tolist() for r in tail] == [[7, 8, 9]]


@pytest.mark.parametrize("form", ["arrays", "lists", "uniform"])
def test_pad_shapes_scatter_matches_row_loop(form):
    """``pad_shapes`` pads 1-D rows with one scatter; it gives what padding each row
    alone gives, and the same error for a row over the max."""
    from petastorm_tpu.loader import _pad_ragged_columns

    rng = np.random.default_rng(7)
    lens = [3] * 6 if form == "uniform" else rng.integers(0, 6, 9).tolist()
    rows = [rng.integers(1, 100, n).astype(np.int64) for n in lens]
    if form == "uniform":
        col = np.stack(rows)
    else:
        col = np.empty(len(rows), dtype=object)
        col[:] = [r.tolist() for r in rows] if form == "lists" else rows
    got = _pad_ragged_columns({"x": col}, {"x": (5,)})
    want = np.zeros((len(rows), 5), np.int64)
    for i, r in enumerate(rows):
        want[i, :len(r)] = r
    np.testing.assert_array_equal(got["x"], want)
    np.testing.assert_array_equal(got["x__mask"],
                                  np.arange(5)[None, :] < np.array(lens)[:, None])
    long = max(range(len(lens)), key=lambda i: (lens[i], -i))
    with pytest.raises(ValueError, match="Row %d of field 'x' has shape \\(%d,\\) "
                       "exceeding" % (long, lens[long])):
        _pad_ragged_columns({"x": col}, {"x": (lens[long] - 1,)})


@pytest.mark.parametrize("kwargs,match", [
    ({"shuffling_queue_capacity": 64}, "shuffling_queue_capacity"),
    ({"device_shuffle_capacity": 64}, "device_shuffle_capacity"),
    ({"pad_shapes": {"input_ids": (L,)}}, "pad_shapes"),
    ({"pack": {"field": "input_ids"}}, "pack="),
    ({"pack": {"field": "input_ids", "length": 0}}, "positive int length"),
])
def test_pack_combinations_raise(tmp_path, kwargs, match):
    url, _ = _write_store(tmp_path / "s", docs=20)
    kwargs.setdefault("pack", {"field": "input_ids", "length": L})
    with make_batch_reader(url, num_epochs=1) as reader:
        with pytest.raises(ValueError, match=match):
            DataLoader(reader, B, **kwargs)


def test_pack_state_dict_and_extra_columns_raise(tmp_path):
    url, _ = _write_store(tmp_path / "s", docs=20)
    reader = make_batch_reader(url, num_epochs=1)
    with DataLoader(reader, B, pack={"field": "input_ids", "length": L}) as loader:
        with pytest.raises(ValueError, match="not checkpointed"):
            loader.state_dict()
    path = tmp_path / "two"
    path.mkdir()
    pq.write_table(pa.table({"id": np.arange(3), "input_ids": pa.array(
        [[1, 2], [3], [4, 5, 6]], pa.list_(pa.int32()))}), str(path / "p.parquet"))
    reader = make_batch_reader("file://" + str(path), num_epochs=1)
    with DataLoader(reader, B, to_device=False,
                    pack={"field": "input_ids", "length": L}) as loader:
        with pytest.raises(ValueError, match="schema_fields"):
            list(loader)
