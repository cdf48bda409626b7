"""Stage-2 programs launched per decoded batch in the traced window:
``PipelineStats.decode_launches / decode_batches`` (one a layout group on the
uniform path, one a canvas bucket on the mixed-size path). None where the program
has no such counters or decoded nothing."""


def value(rec):
    stats = rec["stats"]
    launches, batches = stats.get("decode_launches"), stats.get("decode_batches")
    if launches is None or not batches:
        return None
    return launches / batches
