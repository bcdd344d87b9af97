"""Admission: 90th percentile of the program's ``queue_wait`` spans (submit
until a slot took the request) that ended in the window."""
from chipbench.window import percentile


def read(run):
    waits = [sp["end"] - sp["start"] for sp in run.spans("queue_wait")
             if run.inside(sp["end"])]
    return percentile(waits, 90)
