"""The transfer thread's time in the ``decode.put`` phase of decode dispatch
(``PipelineStats.decode_put_s``: the coefficients' ``device_put``) over the traced
window. None where the program has no phases."""


def value(rec):
    seconds = rec["stats"].get("decode_put_s")
    return None if seconds is None else seconds / rec["window_s"]
