"""Program spans (``repro.obs``) on a real profiler trace of the trainer.

A tiny async-refresh trainer runs across two epoch boundaries under
``jax.profiler.start_trace``; the ``.xplane.pb`` it writes is read back with
``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import dataclasses

import jax
import pytest
from jax.profiler import ProfileData

from repro.core.craig import CraigConfig
from repro.data.synthetic import TokenStream
from repro.models import ModelConfig, init_params
from repro.optim import adamw, constant
from repro.train import Trainer, TrainerConfig

CFG = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=128, logit_chunk=16,
)

CATALOG = {
    "trainer.step", "trainer.install", "trainer.batch", "trainer.dispatch",
    "trainer.sync", "refresh.submit", "refresh.job", "refresh.extract",
    "extract.assemble", "refresh.select", "refresh.publish",
}


@dataclasses.dataclass
class Span:
    name: str
    thread: tuple  # (plane, line index): one line per host thread
    start: float
    end: float
    ids: dict

    def covers(self, other: "Span") -> bool:
        return (self.thread == other.thread and self.start <= other.start
                and other.end <= self.end)


def read_spans(trace_dir) -> list[Span]:
    found = sorted(trace_dir.rglob("*.xplane.pb"))
    assert found, f"no .xplane.pb under {trace_dir}"
    out = []
    for pl in ProfileData.from_file(str(found[-1])).planes:
        if not pl.name.startswith("/host:"):
            continue
        for i, ln in enumerate(pl.lines):
            for ev in ln.events:
                if ev.name.startswith("repro."):
                    out.append(Span(ev.name[len("repro."):], (pl.name, i),
                                    ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Steps 1-9 compile everything untraced: step 1 submits v1 on the
    48-doc full-data epoch of six steps, step 7 installs it (a 24-doc
    coreset, three steps an epoch) and submits v2.  Steps 10-13 are traced:
    step 10 installs v2 and submits v3, step 13 installs v3 and submits v4.
    """
    trace_dir = tmp_path_factory.mktemp("trace")
    ds = TokenStream(n_docs=48, seq_len=24, vocab_size=128, n_topics=6)
    tcfg = TrainerConfig(
        batch_size=8, select_every_epochs=1, refresh_mode="async",
        craig=CraigConfig(fraction=0.5, per_class=False),
    )
    t = Trainer(CFG, tcfg, ds, adamw(constant(2e-3)),
                lambda: init_params(jax.random.PRNGKey(0), CFG))
    t.run(9)
    t.refresher.wait()
    jax.profiler.start_trace(str(trace_dir))
    try:
        t.run(4)
        t.refresher.wait()
    finally:
        jax.profiler.stop_trace()
    return t, read_spans(trace_dir)


def test_every_catalog_span_of_the_async_path_is_recorded(traced):
    _, spans = traced
    assert {s.name for s in spans} == CATALOG


def test_step_spans_nest_their_parts_and_carry_the_logged_step(traced):
    t, spans = traced
    steps = [s for s in spans if s.name == "trainer.step"]
    logged = [e["step"] for e in t.metrics_log if e["event"] == "step"]
    assert [s.ids["step"] for s in steps] == logged[9:] == [10, 11, 12, 13]
    for part in ("trainer.batch", "trainer.dispatch", "trainer.sync"):
        inner = [s for s in spans if s.name == part]
        assert len(inner) == 4
        for i, s in enumerate(inner):
            (outer,) = [p for p in steps if p.covers(s)]
            assert outer.ids["step"] == 10 + i


def test_refresh_job_is_tied_to_its_submit_and_install_by_version(traced):
    _, spans = traced
    by = {n: [s for s in spans if s.name == n] for n in CATALOG}
    main = {s.thread for s in by["trainer.step"]}
    assert len(main) == 1
    jobs = {s.ids["version"]: s for s in by["refresh.job"]}
    assert sorted(jobs) == [3, 4]
    assert all(j.thread not in main for j in jobs.values())
    assert [s.ids["version"] for s in by["refresh.submit"]] == [3, 4]
    assert [s.ids["version"] for s in by["trainer.install"]] == [2, 3]
    steps = by["trainer.step"]
    assert all(any(p.covers(s) for p in steps) for s in by["refresh.submit"])
    submit, install = by["refresh.submit"][0], by["trainer.install"][1]
    assert submit.start <= jobs[3].start and jobs[3].end <= install.end
    for name in ("refresh.extract", "refresh.select", "extract.assemble"):
        assert by[name] and all(
            any(j.covers(s) for j in jobs.values()) for s in by[name])
    pubs = by["refresh.publish"]
    assert sorted(p.ids["version"] for p in pubs) == [3, 4]
    assert all(jobs[p.ids["version"]].covers(p) for p in pubs)

