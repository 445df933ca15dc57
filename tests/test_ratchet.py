"""Ratchet gate: hop normalization, green/red verdicts, the run_meta
refusal path, and the edge cases that must warn instead of fail
(missing / renamed / baseline-only hops)."""

import json
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

from benchmarks.ratchet import main, normalize_hop
from kubeflow_rm_tpu.controlplane.obs.runmeta import build_run_meta


def _trace(p50_ms, hops, meta=None):
    art = {
        "mode": "wallclock", "provision_p50_ms": p50_ms,
        "slowest": {"critical_path": [
            {"name": n, "self_ms": ms} for n, ms in hops]},
    }
    if meta is not None:
        art["run_meta"] = meta
    return art


def _meta(**arms):
    return build_run_meta("spawn_conformance",
                          dict({"mode": "wallclock", "shards": 2},
                               **arms))


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


BASE_HOPS = [("provision wc-14", 600.0),
             ("POST /api/namespaces/conf-p2/notebooks", 30.0),
             ("readiness.wait", 180.0),
             ("readiness.wait", 190.0),
             ("admit Notebook", 0.1)]


# ---- normalization ----------------------------------------------------

def test_normalize_scrubs_per_run_identifiers():
    assert normalize_hop("provision wc-14") == "provision wc-*"
    assert normalize_hop("provision wc-3") == "provision wc-*"
    assert normalize_hop("provision chaos-7") == "provision chaos-*"
    assert (normalize_hop("POST /api/namespaces/conf-p2/notebooks")
            == normalize_hop("POST /api/namespaces/conf-p9/notebooks"))
    a = normalize_hop(
        "GET /api/namespaces/conf-p2/notebooks/wc-14/readiness")
    b = normalize_hop(
        "GET /api/namespaces/conf-p8/notebooks/wc-3/readiness")
    assert a == b
    assert normalize_hop("readiness.wait") == "readiness.wait"


# ---- verdicts ---------------------------------------------------------

def test_green_when_within_threshold(tmp_path, capsys):
    base = _trace(1000.0, BASE_HOPS, _meta())
    # different notebook ids, +10% on one hop: inside the gate
    fresh = _trace(1050.0,
                   [("provision wc-3", 660.0),
                    ("POST /api/namespaces/conf-p9/notebooks", 31.0),
                    ("readiness.wait", 370.0),
                    ("admit Notebook", 0.1)], _meta())
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["verdict"] == "ok"
    assert report["regressions"] == []
    # the two readiness.wait hops folded into one matched row
    names = [r["name"] for c in report["comparisons"]
             for r in c["rows"]]
    assert names.count("readiness.wait") == 1
    assert "(provision_p50_ms)" in names


def test_exit_3_when_matched_hop_regresses(tmp_path):
    base = _trace(1000.0, BASE_HOPS, _meta())
    fresh = _trace(1000.0,
                   [("provision wc-3", 900.0),   # +50%, +300ms
                    ("POST /api/namespaces/conf-p9/notebooks", 30.0),
                    ("readiness.wait", 370.0),
                    ("admit Notebook", 0.1)], _meta())
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 3
    report = json.loads(out.read_text())
    assert report["verdict"] == "regressed"
    [bad] = report["regressions"]
    assert bad["name"] == "provision wc-*"
    assert bad["regressed"] is True


def test_exit_3_on_top_level_p50_regression(tmp_path):
    # the 300ms-reconcile-sleep shape: the extra time shows up as a NEW
    # hop (warn only) but the storm p50 regresses -> still gated
    base = _trace(1000.0, BASE_HOPS, _meta())
    fresh_hops = BASE_HOPS + [("reconcile chaos-sleep", 300.0)]
    fresh = _trace(1320.0, fresh_hops, _meta())
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh)])
    assert rc == 3


def test_floor_ms_suppresses_tiny_absolute_regressions(tmp_path):
    # admit hop triples (0.1 -> 0.3ms) — relative blowout, absolute
    # noise; must stay green
    base = _trace(1000.0, BASE_HOPS, _meta())
    fresh = _trace(1010.0,
                   [("provision wc-3", 600.0),
                    ("POST /api/namespaces/conf-p9/notebooks", 30.0),
                    ("readiness.wait", 370.0),
                    ("admit Notebook", 0.3)], _meta())
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh)])
    assert rc == 0


# ---- refusals ---------------------------------------------------------

def test_exit_2_on_arm_mismatch(tmp_path):
    base = _trace(1000.0, BASE_HOPS, _meta(shards=2))
    fresh = _trace(1000.0, BASE_HOPS, _meta(shards=4))
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 2
    report = json.loads(out.read_text())
    assert report["verdict"] == "refused"
    assert any("shards" in r for r in report["refusals"])
    # no garbage deltas computed for the refused pair
    assert report["comparisons"] == []


def test_exit_2_on_harness_mismatch(tmp_path):
    base = _trace(1000.0, BASE_HOPS,
                  build_run_meta("spawn_conformance", {}))
    fresh = _trace(1000.0, BASE_HOPS,
                   build_run_meta("e2e_walk", {}))
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh)])
    assert rc == 2


def test_missing_run_meta_warns_but_compares(tmp_path):
    # checked-in baselines predate stamping: compare, don't refuse
    base = _trace(1000.0, BASE_HOPS)            # no run_meta
    fresh = _trace(1010.0, BASE_HOPS, _meta())
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert any("run_meta missing" in w for w in report["warnings"])
    assert report["comparisons"]


def test_exit_2_on_unreadable_input(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    rc = main(["--baseline-trace", str(bad), "--trace", str(bad)])
    assert rc == 2


def test_exit_2_when_nothing_to_compare():
    assert main([]) == 2
    assert main(["--trace", "only-one-side.json"]) == 2


# ---- warn-not-fail edge cases -----------------------------------------

def test_baseline_only_hop_warns_not_fails(tmp_path):
    base = _trace(1000.0, BASE_HOPS, _meta())
    fresh = _trace(1000.0, BASE_HOPS[:-1], _meta())  # admit vanished
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert any("absent from fresh run" in w
               for w in report["warnings"])


def test_new_hop_warns_not_fails(tmp_path):
    base = _trace(1000.0, BASE_HOPS, _meta())
    fresh = _trace(1000.0, BASE_HOPS + [("wal.replay", 40.0)], _meta())
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert any("absent from baseline" in w
               for w in report["warnings"])


def test_renamed_hop_warns_on_both_sides_not_fails(tmp_path):
    base = _trace(1000.0, BASE_HOPS, _meta())
    renamed = [("readiness.poll" if n == "readiness.wait" else n, ms)
               for n, ms in BASE_HOPS]
    fresh = _trace(1000.0, renamed, _meta())
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-trace", _write(tmp_path, "b.json", base),
               "--trace", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert any("readiness.wait" in w and "fresh" in w
               for w in report["warnings"])
    assert any("readiness.poll" in w and "baseline" in w
               for w in report["warnings"])


# ---- provision-phase comparison ---------------------------------------

def test_provision_pair_accepts_both_phase_key_spellings(tmp_path):
    base = {"run_meta": _meta(),
            "sharded_wal": {"provision_p50_ms": 500.0, "phases": {
                "admit": {"p50_ms_median_of_runs": 10.0},
                "schedule": {"p50_ms_median_of_runs": 50.0}}}}
    fresh = {"run_meta": _meta(),
             "provision_p50_ms": 510.0,
             "phases": {"admit": {"p50_ms": 11.0},
                        "schedule": {"p50_ms": 52.0}}}
    out = tmp_path / "RATCHET.json"
    rc = main(["--baseline-provision",
               _write(tmp_path, "b.json", base),
               "--provision", _write(tmp_path, "f.json", fresh),
               "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    names = {r["name"] for c in report["comparisons"]
             for r in c["rows"]}
    assert {"admit", "schedule", "(provision_p50_ms)"} <= names


def test_provision_phase_regression_gates(tmp_path):
    base = {"run_meta": _meta(),
            "provision_p50_ms": 500.0,
            "phases": {"schedule": {"p50_ms": 200.0}}}
    fresh = {"run_meta": _meta(),
             "provision_p50_ms": 505.0,
             "phases": {"schedule": {"p50_ms": 300.0}}}  # +50%,+100ms
    rc = main(["--baseline-provision",
               _write(tmp_path, "b.json", base),
               "--provision", _write(tmp_path, "f.json", fresh),
               "--floor-ms", "50"])
    assert rc == 3


def test_checked_in_baselines_are_self_green():
    # the ratchet's own identity property: every checked-in artifact
    # compared against itself is green
    rc = main(["--baseline-trace", str(REPO / "TRACE_r01.json"),
               "--trace", str(REPO / "TRACE_r01.json"),
               "--baseline-provision", str(REPO / "PROVISION_r11.json"),
               "--provision", str(REPO / "PROVISION_r11.json")])
    assert rc == 0
