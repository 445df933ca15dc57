"""Find a cell's files by the names in ``BENCHMARK.json``."""

import json
from pathlib import Path

PERF = Path(__file__).resolve().parent
ROOT = PERF.parent


def load() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, dry_run: bool = False) -> dict:
    """Everything one run needs: the cell's entry, its configuration
    (the file as it is run) and its traffic mix, the metrics it
    reports. ``dry_run`` lays the files' ``dry_run`` groups over them:
    the rehearsal's tiny sizes."""
    bench = load()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json ({known})")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (PERF / "traffic" / f"{entry['traffic']}.json").read_text())
    if dry_run:
        over = config.pop("dry_run", {})
        config.update(over.pop("model", {}))
        _overlay(config, over)
        _overlay(traffic, traffic.pop("dry_run", {}))
    return {
        "name": name, "chips": entry["chips"], "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def _overlay(base: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _overlay(base[k], v)
        else:
            base[k] = v
