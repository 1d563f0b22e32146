"""Share of the window the trainer's main loop spent waiting at epoch
boundaries for a refresh to finish: the sum of the ``install_stall_s`` of
the ``craig_refresh`` events the trainer logged in the window, over the
window."""


def read(ctx):
    rec = ctx["record"]
    stalls = [e["install_stall_s"] for e in rec.get("refreshes", [])]
    if not stalls:
        return None
    return 100.0 * sum(stalls) / rec["window_s"]
