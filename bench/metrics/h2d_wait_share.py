"""The transfer thread's time in the ``h2d.wait`` phase of H2D
(``PipelineStats.h2d_wait_s``: the wait for the batch's transfers, and the decoded
outputs it holds, before its host buffers go back to their rings) over the traced
window. None where the program has no phases."""


def value(rec):
    seconds = rec["stats"].get("h2d_wait_s")
    return None if seconds is None else seconds / rec["window_s"]
