"""The transfer thread's time in the ``decode.pack`` phase of decode dispatch
(``PipelineStats.decode_pack_s``: stage-2 input stacked, zigzag-truncated, split and
packed on the host) over the traced window. None where the program has no phases."""


def value(rec):
    seconds = rec["stats"].get("decode_pack_s")
    return None if seconds is None else seconds / rec["window_s"]
