"""Decode loop: the host's own time from each step's token fetch to the
loop's next program call, waits for work left out, over the window, in
percent, from the engines' ``host_gap_s`` counter, averaged over the
replicas (one loop, on one chip, each). The device has nothing of the
loop's queued meanwhile."""


def read(run):
    if "host_gap_s" not in run.counters_close \
            or not run.count("decode_steps"):
        return None
    return 100.0 * run.count("host_gap_s") / (run.chips * run.window_s)
