"""The ``refresh_latency_s.train`` reader on built records."""
from __future__ import annotations

import pytest

from chipbench import harness

READ = harness.metric_module("refresh_latency_s.train").read


def refresh(select_time_s):
    return {"event": "craig_refresh", "select_time_s": select_time_s,
            "install_stall_s": 0.0}


@pytest.mark.parametrize("times, median", [
    ([7.906], 7.906),
    ([7.907, 7.906, 9.5], 7.907),
    ([7.9, 8.1], 8.0),
])
def test_median_of_the_window_refreshes(times, median):
    rec = {"window_s": 45.0, "refreshes": [refresh(t) for t in times]}
    assert READ({"record": rec}) == pytest.approx(median)


@pytest.mark.parametrize("refreshes", [None, []])
def test_nothing_to_read_is_none(refreshes):
    rec = {"window_s": 45.0}
    if refreshes is not None:
        rec["refreshes"] = refreshes
    assert READ({"record": rec}) is None
