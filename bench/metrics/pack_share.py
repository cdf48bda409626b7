"""The producer's time packing documents into rows (``PipelineStats.pack_s``:
chunking, first-fit placement and the token copy) over the traced window. None
where the program does not pack."""


def value(rec):
    seconds = rec["stats"].get("pack_s")
    return None if seconds is None else seconds / rec["window_s"]
