"""Admission: real prompt rows over the rows the program computed for
them, in percent, in the window (traced runs; read from spans).

A padded prefill computes ``slots`` rows for the group it admits: each of
its requests' ``prefill`` spans (mode ``batched``) carries the group's
size. A chunk step computes one row when one slot is chunking and ``slots``
rows when several are (the batched chunk pads its rows): the chunk events
that one step emits come together, a decode step apart from the next
step's, so they are grouped by time."""

STEP_GAP_S = 1e-3


def read(run):
    slots = int(run.deployment["slots"])
    real = computed = 0.0
    times = []
    for sp in run.spans("prefill"):
        if sp["attrs"].get("mode") == "batched" and run.inside(sp["start"]):
            real += 1
            computed += slots / sp["attrs"].get("group", 1)
        for t, name, _ in sp["events"]:
            if name == "chunk" and run.inside(t):
                times.append(t)
    times.sort()
    group = 1
    for a, b in zip(times, times[1:] + [None]):
        if b is not None and b - a < STEP_GAP_S:
            group += 1
            continue
        real += group
        computed += slots if group > 1 else 1
        group = 1
    return 100.0 * real / computed if computed else None
