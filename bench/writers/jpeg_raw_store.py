"""ImageNet-shaped JPEG Parquet store at original, mixed sizes, made from the seed.

Each row's size comes from the configuration's ``size_mixture`` (a share of rows
each: a fixed long side with a uniform short side and a landscape share, both sides
uniform, or a uniform long side with a uniform short-to-long ratio), drawn from
``(seed, id)``. Its content is ``jpeg_store``'s image model at that size (a blurred
noise field over a gradient, one hard-edged patch and fine texture). cv2 encodes at
``jpeg_quality`` with 4:2:0 chroma and pyarrow writes files of ``rows_per_file`` rows,
row groups cut to ``row_group_mib`` of in-memory rows; program code writes only
``_common_metadata``, whose image field has the variable shape ``(None, None, 3)``.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def row_size(mixture, rng):
    """One row's ``(height, width)`` from the mixture, drawn from ``rng``."""
    u = rng.random()
    for group in mixture:
        u -= group["share"]
        if u < 0:
            break

    def uniform(lo_hi):
        return int(rng.integers(lo_hi[0], lo_hi[1] + 1))

    if "both" in group:
        return uniform(group["both"]), uniform(group["both"])
    long = uniform(group["long"])
    if "short" in group:
        short = uniform(group["short"])
    else:
        short = int(round(long * rng.uniform(*group["short_ratio"])))
    return (short, long) if rng.random() < group["landscape"] else (long, short)


def _image(cfg, seed, i):
    import cv2

    rng = np.random.default_rng([seed, int(i)])
    h, w = row_size(cfg["size_mixture"], rng)
    grad = np.add.outer(np.linspace(0, 255, h, dtype=np.float32),
                        np.linspace(0, 255, w, dtype=np.float32)) * 0.5
    noise = rng.integers(0, 256, (h, w, 3)).astype(np.float32)
    img = 0.45 * cv2.GaussianBlur(noise, (7, 7), 2.0) + 0.35 * grad[..., None]
    side = min(64, min(h, w) // 2)
    y0, x0 = rng.integers(0, min(h, w) - side, 2)
    img[y0:y0 + side, x0:x0 + side] += rng.uniform(-80, 80, 3)  # a hard-edged patch
    img += 0.45 * noise  # fine texture
    params = [int(cv2.IMWRITE_JPEG_QUALITY), int(cfg["jpeg_quality"]),
              int(cv2.IMWRITE_JPEG_SAMPLING_FACTOR),
              int(cv2.IMWRITE_JPEG_SAMPLING_FACTOR_420)]
    ok, buf = cv2.imencode(".jpeg", img.clip(0, 255).astype(np.uint8), params)
    if not ok:
        raise RuntimeError("cv2.imencode failed for row %d" % i)
    return buf.tobytes()


def _unischema(cfg):
    from petastorm_tpu import types as ptypes
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField

    return Unischema("BenchRawImages", [
        UnischemaField("id", np.int64, (), ScalarCodec(ptypes.LongType()), False),
        UnischemaField("image", np.uint8, (None, None, 3),
                       CompressedImageCodec("jpeg", cfg["jpeg_quality"]), False),
        UnischemaField("label", np.int32, (), ScalarCodec(ptypes.IntegerType()), False),
    ])


def write(root, cfg, seed):
    """Write ``cfg["rows"]`` rows into ``root``; row ``id`` i has label ``i % classes``."""
    import pyarrow as pa
    import pyarrow.fs as pafs
    import pyarrow.parquet as pq

    from petastorm_tpu.metadata import write_petastorm_tpu_metadata

    schema = _unischema(cfg)
    arrow_schema = schema.as_arrow_schema()
    rows, per_file = cfg["rows"], cfg["rows_per_file"]
    groups = {}
    os.makedirs(root)
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        for f, start in enumerate(range(0, rows, per_file)):
            ids = np.arange(start, min(rows, start + per_file), dtype=np.int64)
            blobs = list(pool.map(lambda i: _image(cfg, seed, i), ids))
            table = pa.table({
                "id": ids,
                "image": pa.array(blobs, pa.binary()),
                "label": (ids % cfg["classes"]).astype(np.int32),
            }, schema=arrow_schema)
            per_group = max(1, int(cfg["row_group_mib"] * (1 << 20))
                            // (table.nbytes // len(ids)))
            name = "part-%05d.parquet" % f
            pq.write_table(table, os.path.join(root, name), row_group_size=per_group,
                           compression=cfg["compression"])
            groups[name] = -(-len(ids) // per_group)
    write_petastorm_tpu_metadata(pafs.LocalFileSystem(), root, schema, groups)
