"""``stage1_us_per_image`` reads the loader's stage-1 counters, and reads nothing,
without an error, where a program has none."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_stage1_metric_reads_the_counters():
    sys.path.insert(0, os.path.join(BENCH, "metrics"))
    import stage1_us_per_image as stage1

    assert stage1.value({"stats": {"stage1_images": 4, "stage1_ns": 6_000_000}}) == 1500.0
    # a program without the counters, or a window that staged no image
    assert stage1.value({"stats": {}}) is None
    assert stage1.value({"stats": {"stage1_images": 0, "stage1_ns": 0}}) is None
