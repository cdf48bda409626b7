"""Tokenized C4-shaped Parquet store, made from the seed.

One non-null ``input_ids: list<int32>`` column, one document a row: lengths
``max(min, round(lognormal(mu, sigma)))``, ids ``floor(vocab^u)`` for uniform ``u``
(the rank^-1 law over 1 .. vocab-1; 0 is the pad id and never written). Row groups
are cut at the first document boundary past ``row_group_mib`` of int32 tokens, as
``make_spark_converter``'s 32 MiB groups; snappy. Written by pyarrow alone.
"""
import os

import numpy as np


def lengths(cfg, seed):
    """The documents' token counts, in store order."""
    spec = cfg["lengths"]
    rng = np.random.default_rng([seed, 0])
    raw = rng.lognormal(spec["lognormal_mu"], spec["lognormal_sigma"], cfg["documents"])
    return np.maximum(spec["min"], np.round(raw)).astype(np.int64)


def group_bounds(lens, cfg):
    """Document index bounds of the row groups: each ends at the first document
    that takes it to ``row_group_mib`` of int32 tokens."""
    budget = int(cfg["row_group_mib"] * (1 << 20)) // 4
    ends = np.cumsum(lens)
    cuts = np.searchsorted(ends, np.arange(budget, ends[-1], budget), side="left") + 1
    return np.unique(np.concatenate([[0], cuts, [len(lens)]]))


def write(root, cfg, seed):
    import pyarrow as pa
    import pyarrow.parquet as pq

    lens = lengths(cfg, seed)
    bounds = group_bounds(lens, cfg)
    rng = np.random.default_rng([seed, 1])
    log_vocab = np.log(cfg["vocab_size"])
    field = pa.field(cfg["field"], pa.list_(pa.int32()), nullable=False)
    os.makedirs(root)
    with pq.ParquetWriter(os.path.join(root, "part-00000.parquet"), pa.schema([field]),
                          compression=cfg["compression"]) as writer:
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            n = int(lens[lo:hi].sum())
            ids = np.floor(np.exp(rng.random(n) * log_vocab)).astype(np.int32)
            np.clip(ids, 1, cfg["vocab_size"] - 1, out=ids)
            offsets = np.concatenate([[0], np.cumsum(lens[lo:hi])]).astype(np.int32)
            col = pa.ListArray.from_arrays(pa.array(offsets), pa.array(ids))
            writer.write_table(pa.Table.from_arrays([col], schema=pa.schema([field])),
                               row_group_size=hi - lo)
