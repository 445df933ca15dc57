#!/usr/bin/env python3
"""Perf ratchet: baseline-diff a fresh storm against checked-in
artifacts, and FAIL the build when a matched hop regresses.

Turns tracing from a debugging tool into enforcement (ROADMAP item 3):
the checked-in ``TRACE_r01.json`` / ``PROVISION_r11.json`` record what
the spawn path cost when they were cut; this tool compares a fresh
storm's trace critical-path hops and PhaseRecorder percentiles against
them and exits 3 — the repo's established gate-failure code, same as
the lockgraph gate — when any matched hop regressed more than
``--threshold`` (default 20%) AND more than ``--floor-ms`` (absolute
noise floor: a 0.1ms hop doubling is not a regression).

Hop matching normalizes per-run identifiers (``wc-14`` -> ``wc-*``,
``/namespaces/conf-p2/`` -> ``/namespaces/*/``) and sums self-time per
normalized name, so the same logical hop matches across runs. Edge
cases degrade to warnings, never spurious failures: a hop present only
in the baseline (vanished or renamed) warns, a hop present only in the
fresh run (new work) warns, and a comparison whose ``run_meta`` arm
flags disagree is REFUSED (exit 2) instead of producing garbage
deltas. Artifacts predating run_meta stamping compare with a warning.

Exit codes: 0 ok, 2 refused / unusable input, 3 regression.

Usage (the CI gate):
    python benchmarks/ratchet.py \
        --baseline-trace TRACE_r01.json --trace TRACE_ci.json \
        --baseline-provision PROVISION_r11.json \
        --provision provision_ci.json --out RATCHET_ci.json
"""

from __future__ import annotations

import argparse
import json
import re
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from kubeflow_rm_tpu.controlplane.obs.runmeta import compatible  # noqa: E402

# per-run identifier scrubbing so "the same hop" matches across storms
_NORMALIZERS = (
    (re.compile(r"\b(wc|nb|chaos|walk|conf-job)-\d+\b"), r"\1-*"),
    (re.compile(r"/namespaces/[^/\s]+/"), "/namespaces/*/"),
    (re.compile(r"/notebooks/[^/\s]+/"), "/notebooks/*/"),
    (re.compile(r"\bchaos-p\d+\b|\bconf-p\d+\b"), "ns-*"),
)


def normalize_hop(name: str) -> str:
    for rx, sub in _NORMALIZERS:
        name = rx.sub(sub, name)
    return name


def _hop_sums(trace_artifact: dict) -> dict[str, float]:
    """self_ms summed per normalized hop name over the slowest trace's
    critical path (several readiness.wait hops fold into one row)."""
    slowest = trace_artifact.get("slowest") or {}
    sums: dict[str, float] = {}
    for hop in slowest.get("critical_path") or []:
        key = normalize_hop(hop.get("name") or "")
        sums[key] = sums.get(key, 0.0) + float(hop.get("self_ms") or 0)
    return sums


def _phase_p50s(artifact: dict) -> dict[str, float]:
    """Per-phase p50 from a provision artifact. Handles both the raw
    PhaseRecorder key (``p50_ms``) and the merged-artifact key
    (``p50_ms_median_of_runs``), and finds the phases dict either at
    top level or inside a named arm section."""
    candidates = [artifact]
    candidates.extend(v for v in artifact.values()
                      if isinstance(v, dict) and "phases" in v)
    out: dict[str, float] = {}
    for c in candidates:
        phases = c.get("phases")
        if not isinstance(phases, dict):
            continue
        for phase, stats in phases.items():
            if not isinstance(stats, dict):
                continue
            p50 = stats.get("p50_ms",
                            stats.get("p50_ms_median_of_runs"))
            if p50 is not None:
                out[phase] = float(p50)
        break  # first section with phases wins (top level preferred)
    return out


def _top_level_p50(artifact: dict) -> float | None:
    v = artifact.get("provision_p50_ms")
    if v is not None:
        return float(v)
    for sec in artifact.values():
        if isinstance(sec, dict) and "provision_p50_ms" in sec:
            return float(sec["provision_p50_ms"])
    return None


def _compare(kind: str, base: dict[str, float], fresh: dict[str, float],
             threshold: float, floor_ms: float
             ) -> tuple[list[dict], list[str], list[dict]]:
    """(matched rows, warnings, regressions) for one metric table."""
    rows, warnings, regressions = [], [], []
    for name in sorted(set(base) | set(fresh)):
        b, f = base.get(name), fresh.get(name)
        if b is None:
            warnings.append(f"{kind} '{name}' absent from baseline "
                            f"(new hop?) — not gated")
            continue
        if f is None:
            warnings.append(f"{kind} '{name}' absent from fresh run "
                            f"(vanished or renamed?) — not gated")
            continue
        delta = f - b
        pct = (delta / b * 100.0) if b > 0 else (
            0.0 if delta <= 0 else float("inf"))
        row = {"kind": kind, "name": name, "baseline_ms": round(b, 2),
               "fresh_ms": round(f, 2), "delta_ms": round(delta, 2),
               "delta_pct": round(pct, 1) if pct != float("inf")
               else None}
        regressed = (delta > floor_ms
                     and (b <= 0 or delta / b > threshold))
        row["regressed"] = regressed
        rows.append(row)
        if regressed:
            regressions.append(row)
    return rows, warnings, regressions


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="perf ratchet: fail on >threshold regressions vs "
                    "checked-in baselines")
    ap.add_argument("--baseline-trace", default="",
                    help="checked-in trace artifact (TRACE_r01.json)")
    ap.add_argument("--trace", default="",
                    help="fresh storm's --trace-out artifact")
    ap.add_argument("--baseline-provision", default="",
                    help="checked-in provision artifact "
                         "(PROVISION_r11.json)")
    ap.add_argument("--provision", default="",
                    help="fresh storm's --out artifact")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="relative regression gate (0.20 = 20%%)")
    ap.add_argument("--floor-ms", type=float, default=150.0,
                    help="absolute delta a hop must also exceed — "
                         "single-trace self_ms attribution jitters by "
                         "tens of ms run-to-run; sub-floor deltas "
                         "never fail the gate")
    ap.add_argument("--out", default="",
                    help="write the comparison report JSON here")
    args = ap.parse_args(argv)

    pairs = []
    if bool(args.baseline_trace) != bool(args.trace):
        print("ratchet: --baseline-trace and --trace go together",
              file=sys.stderr)
        return 2
    if bool(args.baseline_provision) != bool(args.provision):
        print("ratchet: --baseline-provision and --provision go "
              "together", file=sys.stderr)
        return 2
    if args.trace:
        pairs.append(("trace", args.baseline_trace, args.trace))
    if args.provision:
        pairs.append(("provision", args.baseline_provision,
                      args.provision))
    if not pairs:
        print("ratchet: nothing to compare (pass --trace/--provision)",
              file=sys.stderr)
        return 2

    report: dict = {"threshold": args.threshold,
                    "floor_ms": args.floor_ms,
                    "comparisons": [], "warnings": [],
                    "refusals": [], "regressions": []}
    for kind, base_path, fresh_path in pairs:
        try:
            base, fresh = _load(base_path), _load(fresh_path)
        except (OSError, ValueError) as e:
            print(f"ratchet: cannot load {kind} pair: {e}",
                  file=sys.stderr)
            return 2
        refusals, warnings = compatible(base.get("run_meta"),
                                        fresh.get("run_meta"))
        report["refusals"].extend(f"{kind}: {r}" for r in refusals)
        report["warnings"].extend(f"{kind}: {w}" for w in warnings)
        if refusals:
            continue
        if kind == "trace":
            base_t, fresh_t = _hop_sums(base), _hop_sums(fresh)
            # the whole-storm p50 rides the trace artifact: gate it as
            # a synthetic hop so a regression spread thinly over many
            # hops (or parked on a NEW hop, which only warns) still
            # trips the ratchet
            bp, fp = _top_level_p50(base), _top_level_p50(fresh)
            if bp is not None and fp is not None:
                base_t["(provision_p50_ms)"] = bp
                fresh_t["(provision_p50_ms)"] = fp
        else:
            base_t, fresh_t = _phase_p50s(base), _phase_p50s(fresh)
            bp, fp = _top_level_p50(base), _top_level_p50(fresh)
            if bp is not None and fp is not None:
                base_t["(provision_p50_ms)"] = bp
                fresh_t["(provision_p50_ms)"] = fp
        rows, warnings, regressions = _compare(
            kind, base_t, fresh_t, args.threshold, args.floor_ms)
        report["comparisons"].append(
            {"kind": kind, "baseline": base_path, "fresh": fresh_path,
             "rows": rows})
        report["warnings"].extend(warnings)
        report["regressions"].extend(regressions)

    if report["refusals"]:
        report["verdict"] = "refused"
    elif report["regressions"]:
        report["verdict"] = "regressed"
    else:
        report["verdict"] = "ok"

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    for w in report["warnings"]:
        print(f"ratchet: warn: {w}", file=sys.stderr)
    for r in report["refusals"]:
        print(f"ratchet: REFUSED: {r}", file=sys.stderr)
    if report["verdict"] == "refused":
        print("RATCHET REFUSED (mismatched arms — fix the comparison, "
              "don't trust these deltas)", file=sys.stderr)
        return 2
    if report["verdict"] == "regressed":
        print("RATCHET GATE FAILED:", file=sys.stderr)
        for r in report["regressions"]:
            print(f"  {r['kind']} '{r['name']}': "
                  f"{r['baseline_ms']}ms -> {r['fresh_ms']}ms "
                  f"(+{r['delta_pct']}%)", file=sys.stderr)
        return 3
    matched = sum(len(c["rows"]) for c in report["comparisons"])
    print(f"RATCHET OK ({matched} matched hops/phases within "
          f"{int(args.threshold * 100)}%, "
          f"{len(report['warnings'])} warnings)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
