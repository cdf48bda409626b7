"""The reader pool's stage-1 JPEG entropy decode, per image: ``PipelineStats``
``stage1_ns / stage1_images / 1000`` over the traced window, the decoding threads'
CPU time (``time.thread_time_ns``) in microseconds. None where the program has no
stage-1 counters or staged no image in the window."""


def value(rec):
    stats = rec["stats"]
    images, ns = stats.get("stage1_images"), stats.get("stage1_ns")
    if not images or ns is None:
        return None
    return ns / images / 1000
