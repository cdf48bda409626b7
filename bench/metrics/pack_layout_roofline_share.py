"""The packed layout program's share of the HBM roofline, in percent: the bytes
``jit_ptpu_pack_layout`` must move over its device seconds times the chip's peak
HBM bandwidth (``bench/peaks.json``), bytes-bound (it does no arithmetic to speak
of).

Device seconds: the mean over the cell's chips of ``loader_s``, the device time of
programs not named ``bench_*``; in the packed-token cell ``jit_ptpu_pack_layout`` is
the only such program. Bytes: :func:`layout_bytes` of the window's
``PipelineStats.pack_slots`` and ``pack_chunks``. None where the program does not
pack or the trace holds no loader time."""
import json
import os


def layout_bytes(slots, chunks):
    """Segment ids and positions written (two int32 a slot) plus the chunk table
    read (three int32 a chunk)."""
    return 8 * slots + 12 * chunks


def value(rec):
    stats = rec["stats"]
    devices = rec.get("trace", {}).get("devices")
    if "pack_slots" not in stats or not devices:
        return None
    seconds = sum(d["loader_s"] for d in devices) / len(devices)
    if seconds <= 0:
        return None
    import jax

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    return 100.0 * layout_bytes(stats["pack_slots"], stats["pack_chunks"]) / (seconds * peak)
