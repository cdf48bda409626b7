"""Host-to-device bytes per row: what the ``h2d.put`` and ``decode.put`` phases
shipped in the window (``PipelineStats.h2d_bytes``: the batch's columns and the JPEG
coefficients) over the window's rows. None where the program has no phases."""


def value(rec):
    nbytes = rec["stats"].get("h2d_bytes")
    return None if nbytes is None else nbytes / rec["rows"]
