"""One command, one cell, one run.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that owns the chip: finds the cell's configuration,
traffic mix and metrics by name from ``BENCHMARK.json``, builds the
weights on the device from the seed, warms up the cell's own shapes
(set-up), measures for ``--seconds``, then checks what the timed path
produced against the plain reference in ``perf/reference.py`` and
prints one JSON line last. ``--trace 0`` reports the end-to-end
metrics, ``--trace 1`` the per-layer ones and a breakdown. It knows
two kinds of cell, ``serve`` and ``train`` (``perf/kinds/``), named
by the traffic file, and nothing of any cell's name.

Without a TPU it exits non-zero and prints no result.
``--cpu-dry-run`` is the rehearsal, never a fallback: the same code
at a tiny size on the CPU, and it prints no time, rate or share.
"""

import argparse
import faulthandler
import importlib
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: dump every thread's stack and exit rather than outlast the driver
DEADLINE_S = 1150


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="rehearse at a tiny size on the CPU")
    ap.add_argument("--control", default=None,
                    help="put the control in the program's place in the "
                         "comparison: the reference at this precision "
                         "(int8) or, kind train, with the fault half-batch "
                         "planted; correct then has to come out false")
    ap.add_argument("--sweep", default=None,
                    help="kind serve, bring-up only: a window at each of "
                         "these rates (comma-separated; rate@n orders the "
                         "sizes by schedule_seed n) after one set-up; "
                         "no comparison, no result line of the contract's")
    ap.add_argument("--dump-trace", default=None,
                    help="write the trace's planes, lines and top "
                         "event names to this file")
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from perf import harness, manifest
    cell = manifest.cell(args.workload, dry_run=dry)
    if args.seconds is None:
        args.seconds = float(manifest.load()["run_seconds"])

    from kubeflow_rm_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    # keep every program, the small ones too: a run after the first
    # then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    want = "cpu" if dry else "tpu"
    if devices[0].platform != want or len(devices) < cell["chips"]:
        print(f"perf/run.py: cell {cell['name']} needs {cell['chips']} "
              f"{want} device(s), jax found {len(devices)} x "
              f"{devices[0].platform!r} ({devices[0].device_kind})",
              file=sys.stderr)
        return 1
    devices = devices[:cell["chips"]]
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True,
                                      file=sys.__stderr__)
    print(f"perf/run.py: {cell['name']} seed {args.seed} seconds "
          f"{args.seconds} trace {args.trace} on "
          f"{harness.device_json(devices)}; compile cache {cache_dir}",
          file=sys.stderr)

    kind = importlib.import_module(f"perf.kinds.{cell['traffic']['kind']}")
    clock = harness.CompileClock()
    try:
        out = kind.run(cell=cell, args=args, devices=devices, clock=clock,
                       t_start=T_START, dry=dry)
    finally:
        clock.close()
        faulthandler.cancel_dump_traceback_later()

    if "sweep" in out:
        print(json.dumps(out))
        return 0
    compared = out["compared"]
    compared["compiles_in_window"] = {
        "value": out["compiles_in_window"], "limit": 0}
    correct = harness.verdict(compared)
    device = harness.device_json(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    names = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {m["name"]: {"value": out["metrics"][m["name"]],
                           "unit": m["unit"]}
               for m in names if m["name"] in out["metrics"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and out.get("trace"):
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
        line["breakdown"] = harness.breakdown(out["trace"])
    if dry:
        # the rehearsal carries counts, never a time, a rate or a share
        line["cpu_dry_run"] = True
        line["metrics"] = {k: {"value": None, "unit": v["unit"]}
                           for k, v in metrics.items()}
        device.pop("busy_s", None)
        device.pop("window_s", None)
        line.pop("breakdown", None)
        # (a dict under such a name is kept: its kind left only counts)
        out["info"] = {k: v for k, v in out["info"].items()
                       if isinstance(v, dict)
                       or not k.endswith(("_ms", "_s", "_ms_max"))}
    line["info"] = out["info"]
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
