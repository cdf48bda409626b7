"""The share of the 8x8 blocks the device decode ran that were canvas padding:
``PipelineStats`` ``(decode_canvas_blocks - decode_real_blocks) /
decode_canvas_blocks`` over the traced window (0 where every batch decodes at its
own layout). None where the program has no canvas counters or decoded nothing."""


def value(rec):
    stats = rec["stats"]
    canvas, real = stats.get("decode_canvas_blocks"), stats.get("decode_real_blocks")
    if not canvas or real is None:
        return None
    return (canvas - real) / canvas
