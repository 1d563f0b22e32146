"""Share of the device's busy time spent in the refresh's programs: proxy
extraction and the device-engine greedy, by program name in the trace."""


def read(ctx):
    tr = ctx["trace"]
    busy = tr["busy_s"]
    progs = tr["program_s"]
    t = progs.get("extract", 0.0) + progs.get("greedy", 0.0)
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
