"""The transfer thread's time in the ``h2d.put`` phase of H2D
(``PipelineStats.h2d_put_s``: one ``device_put`` per column) over the traced window.
None where the program has no phases."""


def value(rec):
    seconds = rec["stats"].get("h2d_put_s")
    return None if seconds is None else seconds / rec["window_s"]
