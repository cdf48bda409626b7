"""The transfer thread's time in the ``decode.launch`` phase of decode dispatch
(``PipelineStats.decode_launch_s``: the stage-2 call, the BGR flip and a reshard) over
the traced window. None where the program has no phases."""


def value(rec):
    seconds = rec["stats"].get("decode_launch_s")
    return None if seconds is None else seconds / rec["window_s"]
