"""Load generator: 99th percentile, over the requests due in the window,
of how late each was sent: send time less due time (an open loop's
scheduled arrival, a closed loop's releasing completion)."""
from chipbench.window import percentile


def read(run):
    return percentile(((s.submit - s.due) * 1e3
                       for s in run.due_in_window()), 99)
