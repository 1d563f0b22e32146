"""chipbench/trace.py on a built timeline and on a trace recorded on a v5e."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def test_union_length_merges_overlaps():
    assert trace.union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace.union_length([]) == 0


def built_planes():
    # window 0..1000 ns; device 0 busy 100-300 and 250-400 (union 300) and
    # an all-reduce 600-700; device 1 busy 0-500
    host = plane("/host:CPU", {"python": [
        ev("bench.window", 0, 1000), ev("bench.feed", 400, 200),
        ev("bench.other", 0, 1000)]})
    d0 = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion.1", 100, 200), ev("_ce_proxy_kernel", 250, 150),
                    ev("all-reduce.3", 600, 100)],
        "XLA Modules": [ev("jit_scan_extract(7)", 100, 300),
                        ev("jit_train_step(3)", 600, 100)]})
    d1 = plane("/device:TPU:1", {"XLA Ops": [ev("fusion.1", 0, 500)],
                                 "XLA Modules": [ev("jit_train_step(3)", 0, 500)]})
    return [host, d0, d1]


def test_reduction_of_a_built_timeline():
    s = trace.reduce_planes(built_planes(), n_devices=2,
                            program_names={"extract": "scan_extract"})
    assert s["window_s"] == pytest.approx(1e-6)
    # busy: device 0 = 300 + 100, device 1 = 500 -> mean 450 ns
    assert s["busy_s"] == pytest.approx(450e-9)
    assert s["idle_share"] == pytest.approx(0.55)
    assert s["collective_s"] == pytest.approx(50e-9)
    assert trace.kernel_s(s, "ce_proxy") == pytest.approx(75e-9)
    assert s["program_s"]["extract"] == pytest.approx(150e-9)
    # device 0's gaps: 0-100 and 700-1000 lie under 'other' only; 400-600
    # under the innermost span 'feed'
    gaps = dict((n, 0.0) for n, _ in s["idle_gaps"])
    for n, sec in s["idle_gaps"]:
        gaps[n] += sec
    assert gaps == pytest.approx({"other": 400e-9, "feed": 200e-9})


def test_a_trace_without_a_window_is_refused():
    planes = built_planes()
    planes[0].lines[0].events = planes[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes(planes)


@pytest.fixture(scope="module")
def v5e():
    """A 0.2 s trace recorded on one TPU v5 lite: five ``ce_proxy`` calls
    (1024 tokens, V = 151936) and five bf16 4096² matmuls on the main
    thread under ``bench.window``, five ``fl_gains_argmax`` sweeps on a
    second thread under ``bench.bg``."""
    return trace.reduce(DATA / "v5e_probe.xplane.pb", n_devices=1,
                        program_names={"jitted": r"^jit_"})


def test_recorded_trace_window_and_busy(v5e):
    assert v5e["n_devices"] == 1
    assert v5e["window_s"] == pytest.approx(0.196403362)
    # every operation ran inside one of the jitted programs
    assert v5e["busy_s"] == pytest.approx(v5e["program_s"]["jitted"], rel=1e-3)
    assert v5e["idle_share"] == pytest.approx(1 - v5e["busy_s"] / v5e["window_s"])
    assert 0.5 < v5e["idle_share"] < 0.65  # the main thread slept 5 x 20 ms


def test_recorded_trace_kernels(v5e):
    # the five ce_proxy_pallas events of the device's 'XLA Ops' line
    assert trace.kernel_s(v5e, r"^ce_proxy_pallas") == pytest.approx(0.054791189)
    assert v5e["top_ops"][0][0].startswith("ce_proxy_pallas")
    assert trace.kernel_s(v5e, r"^fl_gains_argmax_pallas") > 0
    assert v5e["collective_s"] == 0.0
    # the W^T convert and vocab pad the wrapper runs around every call
    assert trace.kernel_s(v5e, r"^(convert|pad\.3)$") == pytest.approx(
        0.014773577 + 0.009420904)
