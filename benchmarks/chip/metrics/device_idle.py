"""Share of the traced window in which no operation ran on the device,
in percent (1 - busy / window, busy from the profiler trace)."""


def read(r):
    t = r["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
