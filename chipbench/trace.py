"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's numbers.

* The window is the host annotation ``bench.window`` that the harness puts
  around the measured loop; everything is clipped to it.
* Device time comes from the device planes (``/device:TPU:<n>``): their
  ``XLA Ops`` line holds one event per operation that ran, their
  ``XLA Modules`` line one per program.
* Busy time is the union of a device's operation intervals, averaged over
  the devices; the idle share is 1 - busy / window.
* Device time is summed per operation and per program; an operation is
  named by its HLO instruction (a Pallas kernel's instruction is named
  after its ``pallas_call`` function, e.g. ``ce_proxy_pallas.1``); loops
  (``while``) count through the operations of their bodies.
* Collective time is the device time of operations whose names say
  all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute,
  send or recv.
* Each idle gap of device 0 is attributed to the innermost ``bench.*`` host
  span that covers its middle (``idle`` where none does).
"""
from __future__ import annotations

import re
import shutil
from pathlib import Path

__all__ = ["clear", "find_xplane", "kernel_s", "reduce", "reduce_planes",
           "union_length"]

_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"\bsend\b|\brecv\b|allreduce|allgather|reducescatter",
    re.IGNORECASE,
)
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def clear(path: Path) -> None:
    shutil.rmtree(path, ignore_errors=True)


def find_xplane(path: Path) -> Path:
    found = sorted(Path(path).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, w0, w1):
    """Idle gaps [(start, end)] of a device inside [w0, w1]."""
    out, t = [], w0
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if t < w1:
        out.append((t, w1))
    return [(s, e) for s, e in out if e > s]


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def op_name(text: str) -> str:
    """An operation's instruction name from its event name, which on a TPU
    is the HLO text (``%ce_proxy_pallas.1 = f32[...] custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text: str) -> str:
    """Instruction name and result type, for the breakdown."""
    name, _, rest = text.partition(" = ")
    return (name.lstrip("%") + (" " + rest.split(" ", 1)[0] if rest else ""))[:120]


_CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def reduce_planes(planes, n_devices: int | None = None,
                  program_names: dict | None = None) -> dict:
    """The reduction, from parsed planes (``ProfileData.planes``)."""
    host_spans, windows = [], []
    device_lines: dict[str, dict[str, list]] = {}
    for pl in planes:
        if _DEVICE_PLANE.match(pl.name):
            lines = {}
            for ln in pl.lines:
                lines[ln.name] = list(_events(ln))
            device_lines[pl.name] = lines
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                for name, s, e in _events(ln):
                    if name == "bench.window":
                        windows.append((s, e))
                    elif name.startswith("bench."):
                        host_spans.append((name[len("bench."):], s, e))
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    window_ns = w1 - w0
    devs = sorted(device_lines, key=lambda n: int(n.rsplit(":", 1)[1]))
    if n_devices is not None:
        devs = devs[:n_devices]
    if not devs:
        raise ValueError("the trace holds no device plane")

    def clip(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]

    op_time: dict[str, float] = {}
    module_time: dict[str, float] = {}
    busy, collective = [], 0.0
    gaps0 = []
    for i, dev in enumerate(devs):
        lines = device_lines[dev]
        ops = clip(lines.get("XLA Ops", []))
        mods = clip(lines.get("XLA Modules", []))
        if not ops:
            ops = mods
        for n, s, e in ops:
            if _CONTAINERS.match(op_name(n)):
                continue  # a loop's body operations are events of their own
            op_time[n] = op_time.get(n, 0.0) + (e - s)
            if _COLLECTIVE.search(op_name(n)):
                collective += e - s
        for n, s, e in mods:
            module_time[n] = module_time.get(n, 0.0) + (e - s)
        iv = [(s, e) for _, s, e in ops]
        busy.append(union_length(iv))
        if i == 0:
            gaps0 = _gaps(iv, w0, w1)
    nd = len(devs)

    def attribute(mid):
        best = None
        for name, s, e in host_spans:
            if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return "idle" if best is None else best[0]

    gap_list = sorted(((attribute((s + e) / 2), (e - s) / 1e9) for s, e in gaps0),
                      key=lambda g: -g[1])
    idle_by_host: dict[str, float] = {}
    for name, sec in gap_list:
        idle_by_host[name] = idle_by_host.get(name, 0.0) + sec
    by_label: dict[str, float] = {}
    for n, t in op_time.items():
        by_label[op_label(n)] = by_label.get(op_label(n), 0.0) + t
    top_ops = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    programs = {}
    for label, pattern in (program_names or {}).items():
        rx = re.compile(pattern)
        programs[label] = sum(t for n, t in module_time.items() if rx.search(n)) / 1e9 / nd
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / nd / 1e9,
        "idle_share": 1.0 - (sum(busy) / nd) / window_ns,
        "n_devices": nd,
        "op_s": _by_name(op_time, nd),
        "module_s": {n: t / 1e9 / nd for n, t in module_time.items()},
        "program_s": programs,
        "collective_s": collective / 1e9 / nd,
        "top_ops": [[n, t / 1e9 / nd] for n, t in top_ops],
        "idle_gaps": [[n, s] for n, s in gap_list[:10]],
        "idle_by_host": idle_by_host,
    }


def _by_name(op_time: dict, nd: int) -> dict:
    out: dict[str, float] = {}
    for n, t in op_time.items():
        out[op_name(n)] = out.get(op_name(n), 0.0) + t / 1e9 / nd
    return out


def reduce(xplane: Path, n_devices: int | None = None,
           program_names: dict | None = None) -> dict:
    """Reduce the trace file; see the module docstring."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    return reduce_planes(list(pd.planes), n_devices, program_names)


def kernel_s(summary: dict, pattern: str) -> float:
    """Device seconds of the operations whose instruction names match
    ``pattern`` (a Pallas kernel's instruction is named after its
    ``pallas_call`` function, e.g. ``ce_proxy_pallas.1``)."""
    rx = re.compile(pattern)
    return sum(t for n, t in summary["op_s"].items() if rx.search(n))
