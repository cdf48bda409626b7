"""The crop policy of ``DataLoader(device_decode_resize=...)`` and its host half.

A policy says how a decoded image becomes the delivered ``size``: the whole image
resized (``crop="whole"``, what an ``(h, w)`` pair means), or torchvision's
``RandomResizedCrop`` (``crop="random_resized"``: an area share in ``scale`` and an
aspect ratio in ``ratio``, 10 attempts, then the center-crop fallback) with a random
horizontal flip of probability ``flip``. Boxes are drawn here, on the host, from each
image's header size, with a numpy Philox generator keyed by ``(seed, batch number)``
and vectorised over the batch; the device resamples each image inside its box
(:func:`petastorm_tpu.ops.jpeg.decode_jpeg_crop_batch`). A box is one int32 row:
``y0, x0, h, w, flip``.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

#: torchvision's attempts before the center-crop fallback
ATTEMPTS = 10
_KEYS = ("size", "crop", "scale", "ratio", "flip")


class CropPolicy(NamedTuple):
    size: tuple
    crop: str = "whole"
    scale: tuple = (0.08, 1.0)
    ratio: tuple = (3.0 / 4.0, 4.0 / 3.0)
    flip: float = 0.0

    @property
    def draws(self):
        """True when boxes are drawn at random (not the whole image unflipped)."""
        return self.crop != "whole" or self.flip > 0


def _pair(value, label, cast, positive=True):
    try:
        a, b = (cast(v) for v in value)
    except (TypeError, ValueError):
        raise ValueError("device_decode_resize%s must be a pair, got %r" % (label, value))
    if positive and (a <= 0 or b <= 0):
        raise ValueError("device_decode_resize%s must be two positive numbers, got %r"
                         % (label, value))
    return a, b


def parse_policy(spec, label=""):
    """An ``(h, w)`` pair or a policy dict → :class:`CropPolicy`; ValueError names
    what is wrong. A pair is ``{"size": (h, w)}``: the whole image resized."""
    if not isinstance(spec, dict):
        return CropPolicy(_pair(spec, label, int))
    unknown = set(spec) - set(_KEYS)
    if unknown or "size" not in spec:
        raise ValueError("device_decode_resize%s: a policy takes the keys %s and needs "
                         "'size', got %s" % (label, list(_KEYS), sorted(spec)))
    crop = spec.get("crop", "whole")
    if crop not in ("whole", "random_resized"):
        raise ValueError("device_decode_resize%s: crop must be 'whole' or "
                         "'random_resized', got %r" % (label, crop))
    scale = _pair(spec.get("scale", (0.08, 1.0)), label + "['scale']", float)
    ratio = _pair(spec.get("ratio", (3.0 / 4.0, 4.0 / 3.0)), label + "['ratio']", float)
    if not scale[0] <= scale[1] <= 1.0 or ratio[0] > ratio[1]:
        raise ValueError("device_decode_resize%s: need scale lo <= hi <= 1 and ratio "
                         "lo <= hi, got %r, %r" % (label, scale, ratio))
    flip = float(spec.get("flip", 0.0))
    if not 0.0 <= flip <= 1.0:
        raise ValueError("device_decode_resize%s: flip is a probability, got %r"
                         % (label, flip))
    return CropPolicy(_pair(spec["size"], label + "['size']", int), crop, scale, ratio,
                      flip)


def generator(seed, batch):
    """The numpy Philox generator of batch number ``batch`` under ``seed``."""
    mask = (1 << 64) - 1
    return np.random.Generator(np.random.Philox(
        key=np.array([int(seed) & mask, int(batch) & mask], dtype=np.uint64)))


def draw_boxes(sizes, policy, rng):
    """``(n, 2)`` image heights and widths → ``(n, 5)`` int32 boxes under ``policy``.

    ``rng`` is consumed in one order, whatever the sizes: the ``(n, 10)`` area
    shares, the ``(n, 10)`` log aspect ratios, the ``(n, 2)`` position draws, the
    ``(n,)`` flip draws. Row i takes its first attempt that fits (torchvision's
    ``RandomResizedCrop.get_params``), else the center crop clamped to ``ratio``;
    its top-left corner is ``floor(u * (room + 1))`` of its position draws."""
    sizes = np.asarray(sizes, dtype=np.int64).reshape(-1, 2)
    n = len(sizes)
    height, width = sizes[:, 0:1], sizes[:, 1:2]
    boxes = np.zeros((n, 5), dtype=np.int32)
    if policy.crop == "whole":
        boxes[:, 2], boxes[:, 3] = sizes[:, 0], sizes[:, 1]
        if policy.flip > 0:
            boxes[:, 4] = rng.random(n) < policy.flip
        return boxes
    area = (height * width).astype(np.float64)
    target = area * rng.uniform(policy.scale[0], policy.scale[1], (n, ATTEMPTS))
    aspect = np.exp(rng.uniform(math.log(policy.ratio[0]), math.log(policy.ratio[1]),
                                (n, ATTEMPTS)))
    pos = rng.random((n, 2))
    flips = rng.random(n) < policy.flip
    w = np.round(np.sqrt(target * aspect)).astype(np.int64)
    h = np.round(np.sqrt(target / aspect)).astype(np.int64)
    fits = (w > 0) & (w <= width) & (h > 0) & (h <= height)
    first = np.argmax(fits, axis=1)
    found = fits[np.arange(n), first]
    h, w = h[np.arange(n), first], w[np.arange(n), first]
    # the fallback: the whole image, cut to the nearest end of ``ratio``
    hh, ww = sizes[:, 0], sizes[:, 1]
    in_ratio = ww / hh
    fw = np.where(in_ratio < policy.ratio[0], ww,
                  np.where(in_ratio > policy.ratio[1],
                           np.round(hh * policy.ratio[1]).astype(np.int64), ww))
    fh = np.where(in_ratio < policy.ratio[0],
                  np.round(ww / policy.ratio[0]).astype(np.int64), hh)
    h, w = np.where(found, h, fh), np.where(found, w, fw)
    y0 = np.where(found, np.floor(pos[:, 0] * (hh - h + 1)).astype(np.int64),
                  (hh - h) // 2)
    x0 = np.where(found, np.floor(pos[:, 1] * (ww - w + 1)).astype(np.int64),
                  (ww - w) // 2)
    boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3] = y0, x0, h, w
    boxes[:, 4] = flips
    return boxes


def whole_boxes(sizes):
    """Boxes of whole images, unflipped."""
    return draw_boxes(sizes, CropPolicy((1, 1)), None)


def crop_resize_host(img, box, size):
    """One host-decoded image (stored order) → its box resized to ``size`` with
    ``cv2.resize`` INTER_LINEAR, mirrored left-right when the box says so: the
    sampling the device's crop-resize follows."""
    import cv2

    y0, x0, h, w, flip = (int(v) for v in box)
    out = img[y0:y0 + h, x0:x0 + w]
    if out.shape[:2] != tuple(size):
        out = cv2.resize(np.ascontiguousarray(out), (int(size[1]), int(size[0])),
                         interpolation=cv2.INTER_LINEAR)
    return out[:, ::-1] if flip else out

