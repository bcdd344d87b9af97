"""Device: the share of the traced window in which no operation ran on the
device, from the profiler trace, in percent."""


def read(run):
    p = run.profile
    if p is None or p["busy_s"] is None:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
