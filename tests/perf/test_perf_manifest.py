"""BENCHMARK.json keeps to the benchmark's contract (checked on the
CPU, before any chip time) and every cell's files are found by name."""

import copy
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import check_manifest, flops, manifest  # noqa: E402


def test_manifest_as_committed_is_sound():
    assert check_manifest.check() == []


def test_every_cell_finds_its_files():
    bench = manifest.load()
    for w in bench["workloads"]:
        cell = manifest.cell(w["name"])
        assert cell["traffic"]["kind"] in ("serve", "train")
        assert (manifest.PERF / "kinds" / f"{cell['traffic']['kind']}.py"
                ).is_file()
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"]


def _broken(tmp_path, edit):
    """A copy of the manifest and perf/ with one fault planted."""
    shutil.copytree(ROOT / "perf", tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests" / "perf").mkdir(parents=True)
    b = copy.deepcopy(manifest.load())
    edit(b)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return check_manifest.check(tmp_path)


def _set(path, value):
    def edit(b):
        node = b
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
    return edit


@pytest.mark.parametrize("edit, needle", [
    (_set(("per_layer", 0, "layer"), "fleet gateway"), "layer"),   # PR 22
    (_set(("per_layer", 0, "moves"), "train_tok_s"), "does not report"),
    (_set(("per_layer", 0, "moves"), "nothing"), "no end-to-end"),
    (_set(("end_to_end", 1, "unit"), "tokens per second"), "unit"),
    (_set(("end_to_end", 0, "bound"), 0.2), "bound"),
    (_set(("workloads", 0, "traffic"), "no-such-mix"), "traffic files"),
    (_set(("workloads", 0, "name"), "has space"), "alphabet"),
    (_set(("configs", 0, "reduced"), ["hidden_size"]), "width"),
    (_set(("run_seconds",), 52), "run_seconds"),
    (_set(("per_layer", 1, "why"), "not allowed here"), "not allowed"),
    (_set(("command",), ["python3", "bench.py"]), "outside paths"),
    (_set(("workloads", 0, "chips"), 2), "chips"),
], ids=["layer-alphabet", "moves-other-cell", "moves-unknown", "unit",
        "bound", "traffic-file", "cell-name", "reduced-width",
        "run-seconds", "extra-key", "command-outside", "chips"])
def test_a_planted_fault_is_found(tmp_path, edit, needle):
    if needle == "outside paths":
        (tmp_path / "bench.py").write_text("")
    faults = _broken(tmp_path, edit)
    assert any(needle in f for f in faults), faults


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks on record"):
        flops.peaks("TPU v5")
