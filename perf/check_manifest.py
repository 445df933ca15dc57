"""Check ``BENCHMARK.json`` against the benchmark's contract, on the
CPU, before any chip time is spent: ``python3 perf/check_manifest.py``
prints each fault and exits non-zero where there is one. A test runs
it too. It reads files and never touches jax."""

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*(size|dim)"
                   r"|_dim$|_rank$|head_dim|expansion|experts_per_tok")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def _line(s, what, faults):
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        faults.append(f"{what}: 1 to 200 characters on one line, no tab")


def _only(entry, allowed, what, faults):
    extra = set(entry) - set(allowed)
    missing = set(allowed) - set(entry) - {"workloads"}
    if extra:
        faults.append(f"{what}: keys not allowed: {sorted(extra)}")
    if missing:
        faults.append(f"{what}: keys missing: {sorted(missing)}")


def _under(path: str, paths) -> bool:
    return any(path == p or path.startswith(p + "/") for p in paths)


def check(root: Path = ROOT) -> list[str]:
    faults = []
    file = root / "BENCHMARK.json"
    raw = file.read_bytes()
    if len(raw) > 64 * 1024:
        faults.append("BENCHMARK.json is over 64 KiB")
    b = json.loads(raw)
    if set(b) != KEYS:
        faults.append(f"top-level keys must be exactly {sorted(KEYS)}, "
                      f"got {sorted(b)}")
        return faults

    # paths and command
    paths = b["paths"]
    if not 1 <= len(paths) <= 16:
        faults.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"path {p!r}: a relative path of letters, "
                          f"digits, _ . - /")
        elif not (root / p).is_dir():
            faults.append(f"path {p!r} is not a directory")
    if not 1 <= len(b["command"]) <= 32:
        faults.append("command: 1 to 32 strings")
    for w in b["command"]:
        _line(w, f"command word {w!r}", faults)
        if w.startswith("/") or ".." in w.split("/"):
            faults.append(f"command word {w!r} leaves the repo")
        elif (root / w).exists() and not _under(w, paths):
            faults.append(f"command word {w!r} names a file outside paths")
    if not (isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51):
        faults.append("run_seconds: a whole number from 1 to 51")
    else:
        cells = 24          # later PRs may fill the benchmark
        need = ((2 + 14 * cells) * (b["run_seconds"] + 60)
                + cells * 2 * 90 + 1200)
        if need > 43200:
            faults.append(f"run_seconds {b['run_seconds']}: a full check of "
                          f"24 cells needs {need} s, over 43200")

    # configurations
    if not 1 <= len(b["configs"]) <= 24:
        faults.append("configs: 1 to 24")
    seen_files = set()
    for c in b["configs"]:
        what = f"config {c.get('name')!r}"
        _only(c, ("name", "source", "file", "reduced", "why"), what, faults)
        if not NAME.match(str(c.get("name", ""))):
            faults.append(f"{what}: name outside the alphabet")
        _line(c.get("source"), what + " source", faults)
        _line(c.get("why"), what + " why", faults)
        f = c.get("file", "")
        if not _under(f, paths) or not (root / f).is_file():
            faults.append(f"{what}: file {f!r} is not a file under paths")
        elif f in seen_files:
            faults.append(f"{what}: file {f!r} is another configuration's")
        else:
            seen_files.add(f)
            try:
                body = json.loads((root / f).read_text())
                for k in c.get("reduced", []):
                    if k not in body:
                        faults.append(f"{what}: reduced key {k!r} is not "
                                      f"in {f}")
            except ValueError as e:
                faults.append(f"{what}: {f} is not JSON: {e}")
        reduced = c.get("reduced", [])
        if len(reduced) > 16:
            faults.append(f"{what}: reduced has over 16 keys")
        for k in reduced:
            if not NAME.match(k):
                faults.append(f"{what}: reduced key {k!r} outside the "
                              f"alphabet")
            if WIDTH.search(k):
                faults.append(f"{what}: reduced names a width, {k!r}")
        if not any(w.get("config") == c.get("name") for w in b["workloads"]):
            faults.append(f"{what}: used by no cell")

    # cells
    cells = b["workloads"]
    if not 1 <= len(cells) <= 24:
        faults.append("workloads: 1 to 24 cells")
    config_names = {c.get("name") for c in b["configs"]}
    pairs = set()
    for w in cells:
        what = f"cell {w.get('name')!r}"
        _only(w, ("name", "config", "traffic", "chips", "why"), what, faults)
        for k in ("name", "config", "traffic"):
            if not NAME.match(str(w.get(k, ""))):
                faults.append(f"{what}: {k} outside the alphabet")
        _line(w.get("why"), what + " why", faults)
        if w.get("config") not in config_names:
            faults.append(f"{what}: no configuration {w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            faults.append(f"{what}: chips is 1 or 4")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            faults.append(f"{what}: configuration and traffic appear twice")
        pairs.add(pair)
        mixes = [p for p in (root / "perf" / "traffic").glob(
            f"{w.get('traffic')}.*") if p.suffix in TRAFFIC_SUFFIXES]
        if len(mixes) != 1:
            faults.append(f"{what}: {len(mixes)} traffic files "
                          f"perf/traffic/{w.get('traffic')}.*")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} cells ask for 4 chips, over a quarter")
    cell_names = [w.get("name") for w in cells]

    # metrics
    e2e, per = b["end_to_end"], b["per_layer"]
    if not 1 <= len(e2e) <= 16:
        faults.append("end_to_end: 1 to 16 metrics")
    if not 1 <= len(per) <= 128:
        faults.append("per_layer: 1 to 128 metrics")
    for kind, group in (("metric", [m.get("name") for m in e2e + per]),
                        ("cell", cell_names),
                        ("configuration", [c.get("name")
                                           for c in b["configs"]])):
        twice = {n for n in group if group.count(n) > 1}
        if twice:
            faults.append(f"{kind} names used twice: {sorted(twice)}")

    def cells_of(m):
        return m.get("workloads", cell_names)

    for m in e2e + per:
        what = f"metric {m.get('name')!r}"
        if not NAME.match(str(m.get("name", ""))):
            faults.append(f"{what}: name outside the alphabet")
        if not UNIT.match(str(m.get("unit", ""))):
            faults.append(f"{what}: unit {m.get('unit')!r} is not 1 to 16 "
                          f"of letters, digits, _ / % . -")
        if m.get("better") not in ("lower", "higher"):
            faults.append(f"{what}: better is lower or higher")
        if m.get("source") not in SOURCES:
            faults.append(f"{what}: source is one of {SOURCES}")
        for c in m.get("workloads", []):
            if c not in cell_names:
                faults.append(f"{what}: no cell {c!r}")
    for m in e2e:
        what = f"metric {m.get('name')!r}"
        _only(m, ("name", "unit", "better", "bound", "source", "workloads"),
              what, faults)
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"{what}: an end-to-end metric is taken by the "
                          f"benchmark itself: host_clock or device_trace")
        bound = m.get("bound")
        if not (isinstance(bound, (int, float)) and 0.01 <= bound <= 0.1):
            faults.append(f"{what}: bound from 0.01 to 0.1")
    setup = [m for m in e2e if m.get("name") == "setup_s"]
    if len(setup) != 1 or "workloads" in setup[0]:
        faults.append("setup_s must be an end-to-end metric of every cell")
    e2e_by_name = {m.get("name"): m for m in e2e}
    layers = {}
    for m in per:
        what = f"metric {m.get('name')!r}"
        _only(m, ("name", "unit", "better", "source", "layer", "moves",
                  "workloads"), what, faults)
        if not NAME.match(str(m.get("layer", ""))):
            faults.append(f"{what}: layer {m.get('layer')!r} must be 1 to "
                          f"64 characters from letters, digits, _ . -")
        layers.setdefault(str(m.get("layer", "")).lower(), set()).add(
            m.get("layer"))
        target = e2e_by_name.get(m.get("moves"))
        if target is None:
            faults.append(f"{what}: moves {m.get('moves')!r}, which is no "
                          f"end-to-end metric")
        else:
            for c in cells_of(m):
                if c in cell_names and c not in cells_of(target):
                    faults.append(f"{what}: cell {c!r} does not report "
                                  f"{m.get('moves')!r}")
        name = str(m.get("name", ""))
        if ("roofline" in name or "mfu" in name) and m.get("unit") != "%":
            faults.append(f"{what}: a share of a roofline or a peak has "
                          f"the unit %")
        for ext in (".json", ".py"):
            if not (root / "perf" / "metrics" / (name + ext)).is_file():
                faults.append(f"{what}: no perf/metrics/{name}{ext}")
    for spellings in layers.values():
        if len(spellings) > 1:
            faults.append(f"one layer, several spellings: {sorted(spellings)}")

    # every cell reports setup_s, another end-to-end metric and a
    # per-layer one; a kernel's roofline comes with the step's mfu
    for c in cell_names:
        mine = [m for m in e2e if c in cells_of(m)]
        if len(mine) < 2:
            faults.append(f"cell {c!r}: needs setup_s and one more "
                          f"end-to-end metric")
        layer_mine = [m for m in per if c in cells_of(m)]
        if not layer_mine:
            faults.append(f"cell {c!r}: no per-layer metric")
        for m in layer_mine:
            if "roofline" in m["name"] and not any(
                    "mfu" in re.split(r"[_.\-]", o["name"])
                    and o.get("moves") == m.get("moves")
                    for o in layer_mine):
                faults.append(f"cell {c!r}: {m['name']} moves "
                              f"{m.get('moves')} with no mfu metric "
                              f"beside it that moves the same")
    return faults


def main() -> int:
    faults = check()
    for f in faults:
        print("BENCHMARK.json:", f)
    if not faults:
        print("BENCHMARK.json: ok")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
