"""The original-size ImageNet cell and the host-decode cell on the CPU, outside
tier-1: tiny copies of ``imagenet-1k-raw.devdecode-rrc224-light`` (sizes, canvases
and the crop all cut by eight) and of ``imagenet-jpeg224.hostdecode-light``, added as
new files and entries beside the benchmark's own, rehearse through ``run.run(...,
rehearsal=True, plant=...)`` and read correct; each fault of ``plant_crop.py``
planted under the crop cell reads not correct. Run:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

#: the configuration's mixture at an eighth of its sizes, a store of some 20 row
#: groups, and a 28x28 crop
TINY_RAW = {"rows": 384, "rows_per_file": 128, "row_group_mib": 0.04,
            "size_mixture": [
                {"share": 0.75, "long": [62, 62], "short": [35, 62], "landscape": 0.75},
                {"share": 0.23, "both": [19, 62]},
                {"share": 0.02, "long": [63, 200], "short_ratio": [0.5625, 1.0],
                 "landscape": 0.75}],
            "crop": {"size": [28, 28], "scale": [0.08, 1.0],
                     "ratio": [0.75, 1.3333333333333333], "flip": 0.5},
            "limits": {"rows_unmatched": 0, "rows_out_of_run": 0, "epoch_spread": 3,
                       "image_gap_worst_mean": 4.0, "pixel_gap_max": 40,
                       "pixel_gap_ge2_share": 0.01, "boxes_invalid": 0, "box_area_share_gap": 0.05,
                       "boxes_repeated_share": 0.01}}
TINY_JPEG = {"rows": 256, "rows_per_file": 128, "row_group_mib": 1,
             "limits": {"rows_unmatched": 0, "rows_out_of_run": 0, "epoch_spread": 3,
                        "image_gap_worst_mean": 4.0, "pixel_gap_max": 40}}
#: name -> (config, traffic, config edits, loader edits)
TINY = {
    "tiny-raw.rrc": ("imagenet-1k-raw", "devdecode-rrc224-light", TINY_RAW,
                     {"batch_size": 16,
                      "device_decode_resize": {"image": dict(TINY_RAW["crop"],
                                                             crop="random_resized")}}),
    "tiny-jpeg.host": ("imagenet-jpeg224", "hostdecode-light", TINY_JPEG,
                       {"batch_size": 32}),
}
#: the canvases an eighth of the configuration's
EDGES = (64, 128, 256)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark's files with the tiny cells added as new files and new
    BENCHMARK.json entries only; the program is linked in."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("stores", ".jax_cache", "out",
                                                  "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "petastorm_tpu"), root / "petastorm_tpu")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["traffic"]: w for w in bench["workloads"]}
    for name, (config, traffic, cfg_edit, loader_edit) in TINY.items():
        tiny_cfg, tiny_traffic = name.split(".")
        cfg = json.loads((root / "bench" / "configs" / (config + ".json")).read_text())
        cfg.update(cfg_edit)
        (root / "bench" / "configs" / (tiny_cfg + ".json")).write_text(json.dumps(cfg))
        tr = json.loads((root / "bench" / "traffic" / (traffic + ".json")).read_text())
        tr["reader"]["workers_count"] = 2
        tr["loader"].update(loader_edit)
        (root / "bench" / "traffic" / (tiny_traffic + ".json")).write_text(json.dumps(tr))
        bench["configs"].append(dict(configs[config], name=tiny_cfg,
                                     file="bench/configs/%s.json" % tiny_cfg))
        bench["workloads"].append(dict(cells[traffic], name=name, config=tiny_cfg,
                                       traffic=tiny_traffic))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, workload, fault=None, seed=3000000019):
    args = ["--workload", workload, "--seed", str(seed), "--seconds", "2"]
    code = ("import sys; sys.path.insert(0, 'bench'); import run, plant_crop; "
            "from petastorm_tpu.ops import jpeg; jpeg.CANVAS_EDGES = %r; "
            "run.run(%r, rehearsal=True, plant=%s)"
            % (EDGES, args, "plant_crop.CropPlant(%r)" % fault if fault else None))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


@pytest.mark.parametrize("workload", sorted(TINY))
def test_tiny_cell_is_correct(checkout, workload):
    rc, result, err = _run(checkout, workload)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split()[1] for line in tail] == list(result["checks"])
    if workload == "tiny-raw.rrc":
        assert set(result["checks"]) >= {"boxes_invalid", "box_area_share_gap",
                                         "boxes_repeated_share"}


@pytest.mark.parametrize("fault,number", [
    ("shift_box", "image_gap_worst_mean"),
    ("whole_image", "box_area_share_gap"),
    ("fixed_box", "boxes_repeated_share"),
    ("bf16_crop_decode", "pixel_gap_ge2_share"),
    ("alter_answer", "rows_unmatched"),
    ("alter_answer", "image_gap_worst_mean"),
])
def test_planted_fault_is_not_correct(checkout, fault, number):
    rc, result, err = _run(checkout, "tiny-raw.rrc", fault)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False, result["checks"]
    check = result["checks"][number]
    assert check["value"] > check["limit"], result["checks"]


def test_metrics_read_the_counters():
    sys.path.insert(0, os.path.join(BENCH, "metrics"))
    import crop_decode_roofline_share as roof
    import decode_launches_per_batch as launches
    import decode_pad_share as pad

    stats = {"decode_canvas_blocks": 400, "decode_real_blocks": 300,
             "decode_launches": 6, "decode_batches": 3, "crop_boxes": 10}
    assert pad.value({"stats": stats}) == 0.25
    assert launches.value({"stats": stats}) == 2.0
    # the parent's stats lack the counters: nothing to read, no error
    assert pad.value({"stats": {}}) is None and launches.value({"stats": {}}) is None
    assert roof.value({"stats": {}, "trace": {"devices": [{"loader_s": 1.0}]}}) is None
    assert roof.value({"stats": stats}) is None  # no trace
    assert roof.work_flops(1, 0) == 2 * 64 * 64
    assert roof.work_bytes(0, 1) == 224 * 224 * 3
