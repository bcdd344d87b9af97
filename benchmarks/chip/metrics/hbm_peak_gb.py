"""Device: peak bytes in use on the fullest chip over the run up to the
window's close (``memory_stats``), in GB."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
