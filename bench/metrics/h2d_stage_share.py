"""The transfer thread's time in the ``h2d.stage`` phase of H2D
(``PipelineStats.h2d_stage_s``: the copy into the pinned staging slab) over the
traced window. None where the program has no phases."""


def value(rec):
    seconds = rec["stats"].get("h2d_stage_s")
    return None if seconds is None else seconds / rec["window_s"]
