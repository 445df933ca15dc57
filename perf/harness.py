"""What every kind of cell needs of a run: the compile counter, the
device's description and memory peak, the profiler slice and its
reduction, the per-layer readers, the comparison's verdict and the
result line. Nothing here knows a cell's name."""

import importlib.util
import json
import shutil
import sys
import tempfile
import time

from perf import manifest, trace_reduce


class CompileClock:
    """Counts the executables jax gets, by compiling or by loading
    from the persistent cache (the smoke's ``_CompileClock``). A run
    marks the window's start and end; one inside it fails the run."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self._event = BACKEND_COMPILE_EVENT
        self.programs = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self._event:
            self.programs += 1
            self.seconds += duration

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def device_json(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    """Peak bytes on the fullest chip; None where the backend reports
    none (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class TraceSlice:
    """A few seconds of ``jax.profiler`` inside the window, written
    under ``TMPDIR`` and removed once reduced. The Python tracer is
    off: host spans are jax's own and the harness's annotations."""

    def __init__(self):
        self.dir = None
        self.on = False

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="perf-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def stop(self):
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self, dump: str | None = None) -> dict | None:
        """``{"ops", "modules", "host", "busy_s", "window_s"}`` of the
        first device plane; the window runs from the first device
        operation to the last. ``dump`` writes the planes' and lines'
        names and the most frequent event names there, for a look by
        hand."""
        if self.dir is None:
            return None
        try:
            raw = trace_reduce.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if dump:
            _dump(raw, dump)
        planes = sorted(raw["device"])
        if not planes:
            return None
        busy, window, first = [], [], None
        for name in planes:
            lines = raw["device"][name]
            ops = lines.get("XLA Ops", [])
            b, w = trace_reduce.busy_and_window(ops)
            busy.append(b)
            window.append(w)
            if first is None:
                first = {"ops": ops, "modules": lines.get("XLA Modules", [])}
        return {**first, "host": raw["host"],
                "busy_s": sum(busy) / len(busy), "window_s": max(window)}


def _dump(raw: dict, path: str) -> None:
    out = {"device": {}, "host_top": trace_reduce.time_by_name(
        raw["host"], 60)}
    for plane, lines in raw["device"].items():
        out["device"][plane] = {
            line: {"events": len(ev),
                   "top": trace_reduce.time_by_name(ev, 40)}
            for line, ev in lines.items()}
        # how often each custom call ran, and the programs' runs
        calls = {}
        for name, _start, dur in lines.get("XLA Ops", []):
            if " custom-call(" in name:
                n, t = calls.get(name, (0, 0))
                calls[name] = (n + 1, t + dur)
        out["device"][plane]["custom_calls"] = [
            [trace_reduce.short_name(k), n, t / 1e9]
            for k, (n, t) in calls.items()]
        out["device"][plane]["module_runs"] = [
            [name[:48], start, dur]
            for name, start, dur in lines.get("XLA Modules", [])]
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


def breakdown(trace: dict) -> dict:
    return {"device_ops": trace_reduce.time_by_name(
                trace_reduce.leaves_only(trace["ops"]), 10),
            "idle_gaps": trace_reduce.idle_gaps(trace["ops"],
                                                trace["host"], 10)}


def read_per_layer(cell: dict, run: dict) -> dict:
    """Each of the cell's per-layer metrics through its own reader,
    ``perf/metrics/<name>.py``'s ``read(run, params)``, with
    ``perf/metrics/<name>.json`` as ``params``. A reader that finds
    nothing to read returns None and the metric is left out; what it
    has to say of its arithmetic it leaves under ``run["notes"]``."""
    out = {}
    run.setdefault("notes", {})
    for m in cell["per_layer"]:
        base = manifest.PERF / "metrics" / m["name"]
        params = json.loads(base.with_name(m["name"] + ".json").read_text())
        spec = importlib.util.spec_from_file_location(
            "perf_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            base.with_name(m["name"] + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run, params)
        if value is not None:
            out[m["name"]] = float(value)
    return out


def verdict(compared: dict) -> bool:
    """``compared``: short name -> {"value", "limit"}. Correct where
    every value is a number no greater than its limit. Printed, each
    beside its limit, as the last lines on standard error."""
    ok = True
    for name, c in compared.items():
        good = (c["value"] is not None and c["value"] == c["value"]
                and c["value"] <= c["limit"])
        ok = ok and good
        print(f"compared {name} = {c['value']} limit {c['limit']} "
              f"{'ok' if good else 'FAILED'}", file=sys.stderr)
    return ok and bool(compared)


def sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))
