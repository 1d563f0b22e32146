"""The benchmark's data-driven core: find a cell's files by name, run it, print.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  Each
is a file found by its name:

* ``chipbench/configs/<config>.json`` — the sizes as run, the published
  values of what was cut (``reduced``), what was assumed, and the plain
  reference (``reference``: a module under ``chipbench/reference/``);
* ``chipbench/traffic/<traffic>.json`` — the mix's parameters; its
  ``driver`` names the generator under ``chipbench/drivers/`` that reads
  them (one per kind of traffic, shared by every mix of that kind);
* ``chipbench/limits/<cell>.json`` — the limit of each number the cell's
  correctness comparison prints;
* ``chipbench/metrics/<metric>.py`` — one reader per per-layer metric.

A driver module provides ``setup(cell, seed, devices, log) -> state``,
``window(state, seconds, spans) -> record``, ``check(state, record, log) ->
list[Check]`` and, for ``chipbench/control.py``, ``control(state, log) ->
{number: value}``, read after ``check``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

__all__ = [
    "Cell",
    "Check",
    "Spans",
    "load_cell",
    "load_module",
    "run_cell",
]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by its path (names may hold dots and dashes)."""
    name = "chipbench_" + "".join(c if c.isalnum() else "_" for c in
                                  str(path.relative_to(BENCH_DIR)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file
    traffic: dict  # the traffic file
    limits: dict  # number -> limit
    end_to_end: list  # BENCHMARK.json metric entries reported with --trace 0
    per_layer: list  # BENCHMARK.json metric entries reported with --trace 1
    overrides: dict = dataclasses.field(default_factory=dict)

    def param(self, key: str, default: Any = None) -> Any:
        """A traffic parameter (test overrides first)."""
        if key in self.overrides:
            return self.overrides[key]
        return self.traffic.get(key, default)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: dict | None = None,
              overrides: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with all its files."""
    if manifest is None:
        manifest = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits_path = BENCH_DIR / "limits" / f"{name}.json"
    limits = load_json(limits_path)["limits"] if limits_path.exists() else {}
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in manifest["per_layer"] if _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e,
                per_layer, dict(overrides or {}))


def driver_module(cell: Cell):
    return load_module(BENCH_DIR / "drivers" / f"{cell.traffic['driver']}.py")


def metric_module(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


class Spans:
    """Host spans around the benchmark's calls into the program's layers.

    With ``annotate`` (traced runs) each span is a
    ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so it lands on
    the trace's timeline, where ``trace.py`` attributes device idle gaps to
    the innermost span covering them; otherwise spans cost nothing.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    """One compared number and its limit; ``ok`` when value ≤ limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(math.isfinite(self.value) and self.value <= self.limit)


def limit_of(cell: Cell, name: str) -> float:
    if name not in cell.limits:
        raise KeyError(f"{cell.name}: no limit for {name!r} in chipbench/limits")
    return float(cell.limits[name])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts, while entered, the backend compilations JAX performs
    (persistent-cache misses); the window should see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += float(duration)

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def _metric_entry(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, trace_dir: Path | None = None,
             log: Callable[[str], None] | None = None,
             control: bool = False) -> dict:
    """Set up, measure, check; returns the result line's object.

    ``t_start`` is the host clock (``time.perf_counter``) at process start:
    set-up runs from there to the first timed operation.  With ``control``
    (``chipbench/control.py`` only) the line also carries the control's
    readings of the same numbers under ``control``.
    """
    from chipbench import trace as trace_mod

    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    drv = driver_module(cell)
    spans = Spans(annotate=trace)
    state = drv.setup(cell, seed, devices, log)
    import jax

    summary = None
    if trace:
        trace_dir = trace_dir or (ROOT / ".bench_trace")
        trace_mod.clear(trace_dir)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_start
    with CompileCounter() as compiles, spans.span("window"):
        record = drv.window(state, float(seconds), spans)
    record["compiles"] = compiles.n
    record["compile_s"] = compiles.seconds
    if trace:
        jax.profiler.stop_trace()
    peak = memory_peak(devices)
    if trace:
        summary = trace_mod.reduce(trace_mod.find_xplane(trace_dir),
                                   n_devices=cell.chips,
                                   program_names=cell.param("programs", {}))
        trace_mod.clear(trace_dir)
        top = sorted(summary["module_s"].items(), key=lambda kv: -kv[1])[:12]
        log(f"trace: busy {summary['busy_s']:.3f} s of {summary['window_s']:.3f} s; "
            f"programs {top}; ops {summary['top_ops']}; "
            f"idle by host activity {summary['idle_by_host']}")
    log(f"{cell.name}: set-up {setup_s:.3f} s; window {record['window_s']:.3f} s; "
        f"{record.get('note', '')}; {record['compiles']} compiles in the window "
        f"({record['compile_s']:.3f} s)")
    checks = drv.check(state, record, log)
    control_nums = drv.control(state, log) if control else None
    del state
    gc.collect()

    correct = all(c.ok for c in checks)
    metrics = {}
    if trace:
        ctx = {"cell": cell, "record": record, "trace": summary,
               "peaks": peaks_for(devices[0].device_kind)}
        for m in cell.per_layer:
            v = metric_module(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = _metric_entry(v, m["unit"])
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = _metric_entry(setup_s, "s")
            elif m["name"] in record["e2e"]:
                metrics[m["name"]] = _metric_entry(record["e2e"][m["name"]],
                                                   m["unit"])
    device = device_record(devices[: cell.chips])
    device["memory_peak_bytes"] = peak
    out = {
        "correct": correct,
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["top_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    if control:
        out["control"] = {c.name: {"value": control_nums[c.name], "limit": c.limit}
                          for c in checks if c.name in control_nums}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in chipbench/peaks.json")
    return table[device_kind]
