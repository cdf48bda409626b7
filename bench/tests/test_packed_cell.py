"""The packed-token cell on the CPU, outside tier-1: a tiny ``c4-en-tokenized``
cell, added as new files and entries beside the benchmark's own, rehearses through
``run.run(..., rehearsal=True, plant=...)`` and reads correct; each fault planted
under it reads not correct, its number beside its limit. Run:

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
HERE = os.path.dirname(os.path.abspath(__file__))

#: about 240k tokens in some 30 row groups of 8k: a row group spans several b4
#: batches of 256-token rows, and a 2 s window spans several epochs
TINY_CFG = {"documents": 4000, "rows": 940, "length": 256, "segment_slots": 128,
            "lengths": {"lognormal_mu": 3.59, "lognormal_sigma": 1.0, "min": 4},
            "row_group_mib": 0.03,
            "limits": {"segments_unmatched": 0, "segments_out_of_run": 0,
                       "epoch_spread": 3, "epoch_lead_excess": 0,
                       "layout_errors": 0,
                       "placement_mismatched": 0, "pad_slot_share": None}}
TINY_LOADER = {"batch_size": 4, "pack": {"field": "input_ids", "length": 256}}
CELL = "tiny-c4.pack"


class PackPlant:
    """Faults planted under the packed-token cell, before the check sees a batch."""

    FAULTS = ("one_token", "shift_boundary", "drop_chunk", "drop_half",
              "with_replacement")

    def __init__(self, fault):
        assert fault in self.FAULTS, fault
        self.fault = fault
        self._fn = None

    def before_reader(self):
        if self.fault == "with_replacement":
            import numpy as np

            from petastorm_tpu import plan

            def with_replacement(num_items, epoch, seed, shuffle):
                rng = np.random.default_rng([0 if seed is None else int(seed), int(epoch)])
                return rng.integers(0, num_items, num_items)

            plan.epoch_permutation = with_replacement

    def transform(self, batch):
        if self.fault == "with_replacement":
            return batch
        if self._fn is None:
            import jax

            self._fn = jax.jit(getattr(self, "_" + self.fault))
        return self._fn(batch)

    @staticmethod
    def _parts(batch):
        return batch["input_ids"], batch["input_ids_segment_ids"], batch["input_ids_positions"]

    @staticmethod
    def _out(tok, seg, pos):
        return {"input_ids": tok, "input_ids_segment_ids": seg, "input_ids_positions": pos}

    def _one_token(self, batch):
        tok, seg, pos = self._parts(batch)
        return self._out(tok.at[0, 0].add(1), seg, pos)

    def _shift_boundary(self, batch):
        """Row 0's second segment starts one slot later: its first token joins the
        first segment."""
        import jax.numpy as jnp

        tok, seg, pos = self._parts(batch)
        row = seg[0]
        t = jnp.arange(row.shape[0])
        s2 = jnp.argmax(row == 2)
        moved = (t == s2) & (row == 2)
        new_seg = jnp.where(moved, 1, row)
        new_pos = jnp.where(moved, pos[0][jnp.maximum(s2 - 1, 0)] + 1,
                            jnp.where((row == 2) & (t > s2), pos[0] - 1, pos[0]))
        return self._out(tok, seg.at[0].set(new_seg), pos.at[0].set(new_pos))

    def _drop_chunk(self, batch):
        """Row 0's last segment is dropped (its slots become padding)."""
        import jax.numpy as jnp

        tok, seg, pos = self._parts(batch)
        last = seg[0] == jnp.max(seg[0])
        return self._out(tok.at[0].set(jnp.where(last, 0, tok[0])),
                         seg.at[0].set(jnp.where(last, 0, seg[0])),
                         pos.at[0].set(jnp.where(last, 0, pos[0])))

    @staticmethod
    def _drop_half(batch):
        import jax.numpy as jnp

        def half(v):
            n = v.shape[0] // 2
            return jnp.concatenate([v[:n], v[:v.shape[0] - n]])

        return {k: half(v) for k, v in batch.items()}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark's files with the tiny cell added as new files and new
    BENCHMARK.json entries only; the program is linked in."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("stores", ".jax_cache", "out",
                                                  "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "petastorm_tpu"), root / "petastorm_tpu")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench" / "configs" / "c4-en-tokenized.json").read_text())
    cfg.update(TINY_CFG)
    (root / "bench" / "configs" / "tiny-c4.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench" / "traffic" / "pack8k-b16-light.json").read_text())
    tr["loader"].update(TINY_LOADER)
    (root / "bench" / "traffic" / "pack.json").write_text(json.dumps(tr))
    entry = {c["name"]: c for c in bench["configs"]}["c4-en-tokenized"]
    bench["configs"].append(dict(entry, name="tiny-c4", file="bench/configs/tiny-c4.json"))
    cell = {w["name"]: w for w in bench["workloads"]}["c4-en-tokenized.pack8k-b16-light"]
    bench["workloads"].append(dict(cell, name=CELL, config="tiny-c4", traffic="pack"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, fault=None, seed=3000000019):
    args = ["--workload", CELL, "--seed", str(seed), "--seconds", "2"]
    plant = "test_packed_cell.PackPlant(%r)" % fault if fault else "None"
    code = ("import sys; sys.path.insert(0, 'bench'); sys.path.insert(0, %r); "
            "import run, test_packed_cell; run.run(%r, rehearsal=True, plant=%s)"
            % (HERE, args, plant))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


def test_tiny_packed_cell_is_correct(checkout):
    rc, result, err = _run(checkout)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert set(result["checks"]) == {"segments_unmatched", "segments_out_of_run",
                                     "epoch_spread", "epoch_lead_excess",
                                     "layout_errors",
                                     "placement_mismatched"}
    assert "pad_slot_share" in err  # read as check info, not compared
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split()[1] for line in tail] == list(result["checks"])


@pytest.mark.parametrize("fault,number", [
    ("one_token", "segments_unmatched"),
    ("shift_boundary", "segments_unmatched"),
    ("drop_chunk", "segments_out_of_run"),
    ("drop_half", "segments_out_of_run"),
    ("with_replacement", "epoch_spread"),
    ("with_replacement", "epoch_lead_excess"),
])
def test_planted_fault_is_not_correct(checkout, fault, number):
    rc, result, err = _run(checkout, fault)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False, result["checks"]
    check = result["checks"][number]
    assert check["value"] > check["limit"], result["checks"]
    assert "check %s %s limit %s" % (number, check["value"], check["limit"]) in err


def test_layout_bytes_and_roofline_share():
    sys.path.insert(0, os.path.join(BENCH, "metrics"))
    import pack_layout_roofline_share as m

    assert m.layout_bytes(16 * 8192, 300) == 8 * 131072 + 12 * 300
    assert m.layout_bytes(0, 0) == 0
    # no trace, or a program that does not pack: nothing to read
    assert m.value({"stats": {"pack_slots": 1, "pack_chunks": 1}}) is None
    assert m.value({"stats": {}, "trace": {"devices": [{"loader_s": 1.0}]}}) is None
