"""Plain reference for the original-size JPEG store under a random-resized crop:
pyarrow reads the stored rows, a JPEG header read gives each image's size, and cv2
decodes, crops, resizes and flips. It imports nothing of the program.

Compared, for the delivered stream from its first batch on: every row's id and label
against the store's; row-group runs and epochs (each row once per epoch); every
delivered box against its image's size (a valid RandomResizedCrop draw at the
configuration's ``crop``, or its center-crop fallback); the boxes' mean area share
against the same crop drawn here for the same sizes; boxes that repeat for one id
across deliveries; and, for a seeded sample of the window's batches, every image as
delivered against ``cv2.resize(decode[y0:y0+h, x0:x0+w], 224x224, INTER_LINEAR)``,
mirrored where the box says so: the worst image's mean gap, the widest gap, and the
share of all compared values 2 or more away (the one a decode or resample at
bfloat16 moves: libjpeg's integer IDCT and cv2's fixed-point resize put about 0.4%
of values there, bfloat16 matmuls several times that).
"""
import math
import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import runs

#: boxes drawn here for the expected mean area share, at the least (a few per
#: delivered row in a short stream, one in a long one)
EXPECTED_DRAWS = 100_000


def jpeg_size(blob):
    """``(height, width)`` from a JPEG's start-of-frame marker."""
    pos = 2
    while pos + 4 <= len(blob):
        if blob[pos] != 0xFF:
            pos += 1
            continue
        marker = blob[pos + 1]
        if marker in (0xD8, 0x01) or 0xD0 <= marker <= 0xD7 or marker == 0xFF:
            pos += 1 if marker == 0xFF else 2
            continue
        (seglen,) = struct.unpack(">H", blob[pos + 2:pos + 4])
        if marker in (0xC0, 0xC1, 0xC2):
            return struct.unpack(">HH", blob[pos + 5:pos + 9])
        pos += 2 + seglen
    raise ValueError("no start-of-frame marker")


def fallback_box(height, width, ratio):
    """torchvision's center crop when no attempt fits: ``(y0, x0, h, w)``."""
    in_ratio = width / height
    if in_ratio < ratio[0]:
        w, h = width, int(round(width / ratio[0]))
    elif in_ratio > ratio[1]:
        h, w = height, int(round(height * ratio[1]))
    else:
        h, w = height, width
    return (height - h) // 2, (width - w) // 2, h, w


def draw_box(height, width, scale, ratio, rng):
    """One RandomResizedCrop box, torchvision's ``get_params`` step by step."""
    area = height * width
    for _ in range(10):
        target = area * rng.uniform(scale[0], scale[1])
        aspect = math.exp(rng.uniform(math.log(ratio[0]), math.log(ratio[1])))
        w = int(round(math.sqrt(target * aspect)))
        h = int(round(math.sqrt(target / aspect)))
        if 0 < w <= width and 0 < h <= height:
            top = int(rng.integers(0, height - h + 1))
            return top, int(rng.integers(0, width - w + 1)), h, w
    return fallback_box(height, width, ratio)


def box_valid(box, height, width, scale, ratio):
    """True when some area share in ``scale`` and aspect in ``ratio`` round to the
    box's size, and the box lies in the image; or it is the fallback."""
    y0, x0, h, w, flip = (int(v) for v in box)
    if flip not in (0, 1) or h < 1 or w < 1 or y0 < 0 or x0 < 0 \
            or y0 + h > height or x0 + w > width:
        return False
    if (y0, x0, h, w) == fallback_box(height, width, ratio):
        return True
    area = height * width
    a_lo = max(scale[0] * area, (w - 0.5) * (h - 0.5))
    a_hi = min(scale[1] * area, (w + 0.5) * (h + 0.5))
    r_lo = max(ratio[0], (w - 0.5) / (h + 0.5))
    r_hi = min(ratio[1], (w + 0.5) / (h - 0.5)) if h > 0.5 else ratio[1]
    return a_lo <= a_hi * (1 + 1e-9) and r_lo <= r_hi * (1 + 1e-9)


class Reference:
    def __init__(self, cfg):
        self.cfg = cfg

    @staticmethod
    def identity(batch):
        """What the check keeps of every delivered batch (no device work)."""
        return {"id": batch["id"], "label": batch["label"], "box": batch["image_box"]}

    @staticmethod
    def sample(batch):
        """What the check keeps of a sampled batch."""
        return {"id": batch["id"], "image": batch["image"], "box": batch["image_box"]}

    def check(self, store, idents, samples):
        import cv2
        import pyarrow.parquet as pq

        crop = self.cfg["crop"]
        size, scale, ratio = tuple(crop["size"]), crop["scale"], crop["ratio"]
        ids, labels, blobs, groups = [], [], [], []
        for name in sorted(os.listdir(store)):
            if not name.endswith(".parquet"):
                continue
            pf = pq.ParquetFile(os.path.join(store, name))
            for g in range(pf.num_row_groups):
                t = pf.read_row_group(g, columns=["id", "label", "image"])
                groups.append(t.column("id").to_numpy())
                ids.append(groups[-1])
                labels.append(t.column("label").to_numpy())
                blobs.extend(t.column("image").to_pylist())
        ids, labels = np.concatenate(ids), np.concatenate(labels)
        dims = np.array([jpeg_size(b) for b in blobs], np.int64)
        order = np.argsort(ids)

        def locate(want):
            k = np.minimum(np.searchsorted(ids, want, sorter=order), len(ids) - 1)
            pos = order[k]
            return pos, ids[pos] == want

        got_id = np.concatenate([b["id"] for b in idents]).astype(np.int64)
        got_label = np.concatenate([b["label"] for b in idents])
        got_box = np.concatenate([b["box"] for b in idents]).astype(np.int64)
        pos, found = locate(got_id)
        unmatched = int(np.sum(~found | (labels[pos] != got_label)))
        occ, out_of_run = runs.parse_runs(got_id, groups)

        # boxes: valid draws, their mean area share against this file's own draws
        # for the same sizes, and repeats of one id's box across deliveries
        hw = dims[pos]
        invalid = sum(not box_valid(b, h, w, scale, ratio)
                      for b, (h, w) in zip(got_box, hw))
        share = got_box[:, 2] * got_box[:, 3] / (hw[:, 0] * hw[:, 1])
        rng = np.random.default_rng([len(got_id), 0xC0])
        per_row = min(64, -(-EXPECTED_DRAWS // len(got_id)))
        drawn = [draw_box(int(h), int(w), scale, ratio, rng)
                 for h, w in hw for _ in range(per_row)]
        expected = np.mean(np.array([b[2] * b[3] for b in drawn])
                           / np.repeat(hw[:, 0] * hw[:, 1], per_row))
        seen, repeats, repeated = set(), 0, 0
        counts = {}
        for i, b in zip(got_id.tolist(), map(tuple, got_box.tolist())):
            if counts.get(i):
                repeats += 1
                repeated += (i, b) in seen
            counts[i] = counts.get(i, 0) + 1
            seen.add((i, b))

        def gaps(triple):
            """Mean and widest gap of one delivered image to cv2's decode of the
            stored bytes, cropped, resized and flipped by its box, and how many of
            its values lie 2 or more away; an image that cannot be compared reads
            the widest gap in every value."""
            row_id, img, box = triple
            worst = 255.0, 255, img.size, img.size
            p, ok = locate(np.array([row_id]))
            if not ok[0]:
                return worst
            ref = cv2.imdecode(np.frombuffer(blobs[p[0]], np.uint8), cv2.IMREAD_UNCHANGED)
            y0, x0, h, w, flip = (int(v) for v in box)
            if ref is None or not 0 <= y0 < y0 + h <= ref.shape[0] \
                    or not 0 <= x0 < x0 + w <= ref.shape[1]:
                return worst
            ref = cv2.resize(np.ascontiguousarray(ref[y0:y0 + h, x0:x0 + w]),
                             (size[1], size[0]), interpolation=cv2.INTER_LINEAR)
            if flip:
                ref = ref[:, ::-1]
            if ref.shape != img.shape:
                return worst
            gap = np.abs(ref.astype(np.int16) - img.astype(np.int16))
            return float(gap.mean()), int(gap.max()), int(np.sum(gap >= 2)), gap.size

        triples = [(int(i), img, box) for s in samples
                   for i, img, box in zip(s["id"], s["image"], s["box"])]
        with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
            read = list(pool.map(gaps, triples))
        numbers = {
            "rows_unmatched": unmatched,
            "rows_out_of_run": out_of_run,
            "epoch_spread": runs.epoch_spread(occ, len(groups)),
            "image_gap_worst_mean": max(r[0] for r in read),
            "pixel_gap_max": max(r[1] for r in read),
            "pixel_gap_ge2_share": sum(r[2] for r in read) / max(1, sum(r[3] for r in read)),
            "boxes_invalid": int(invalid),
            "box_area_share_gap": abs(float(share.mean()) - float(expected)),
            "boxes_repeated_share": repeated / repeats if repeats else 0.0,
        }
        limits = self.cfg["limits"]
        failed = unmatched + out_of_run + int(invalid) + sum(
            r[0] > limits["image_gap_worst_mean"] or r[1] > limits["pixel_gap_max"]
            for r in read)
        return {"rows": len(got_id), "failed": int(failed), "numbers": numbers,
                "info": {"images_compared": len(triples), "row_groups_delivered": len(occ),
                         "box_area_share": float(share.mean()),
                         "box_area_share_expected": float(expected),
                         "box_repeats": repeats}}
