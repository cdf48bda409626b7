"""The device decode chain's share of its roofline, in percent: the larger of the
work the decode and crop need over the chip's bf16 peak (``bench/peaks.json``) and
the bytes they must move over its HBM bandwidth, over the chain's device seconds.

Device seconds: ``loader_s``, the mean over the cell's chips of the device time of
programs not named ``bench_*`` (stage-2 in its canvases and the crop-resize). Work
and bytes count what the mathematics needs, from the window's
``PipelineStats.decode_real_blocks`` and ``crop_boxes``, not any padding or dense
interpolation matmul, so the share reads the same whatever implements it (see
:func:`work_flops` and :func:`work_bytes`). None where the program does not count
them or the trace holds no loader time."""
import json
import os

OUT_SAMPLES = 224 * 224 * 3


def work_flops(real_blocks, boxes):
    """Two flops a multiply-add: 64x64 of the inverse DCT a real block, and the
    bilinear taps' 4 an output sample of a channel, 224x224x3 samples a box."""
    return 2 * (64 * 64 * real_blocks + 4 * OUT_SAMPLES * boxes)


def work_bytes(real_blocks, boxes):
    """The real coefficients read (64 int16, 128 bytes a block), the real pixel
    planes written and read once (64 float32 samples a block, each way), and the
    uint8 output written (224x224x3 bytes a box)."""
    return 128 * real_blocks + 2 * 4 * 64 * real_blocks + OUT_SAMPLES * boxes


def value(rec):
    stats = rec["stats"]
    devices = rec.get("trace", {}).get("devices")
    real, boxes = stats.get("decode_real_blocks"), stats.get("crop_boxes")
    if not real or not boxes or not devices:
        return None
    seconds = sum(d["loader_s"] for d in devices) / len(devices)
    if seconds <= 0:
        return None
    import jax

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]
    return 100.0 * max(work_flops(real, boxes) / peak["bf16_flops_per_s"],
                       work_bytes(real, boxes) / peak["hbm_bytes_per_s"]) / seconds
