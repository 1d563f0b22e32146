"""Share of the traced window in which no operation ran on the device:
1 - busy / window, from the trace reduction (``chipbench/trace.py``)."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 100.0 * tr["idle_share"]
