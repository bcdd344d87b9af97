"""Decode loop: generated tokens over decode rows computed (decode steps
times slots), in the window, in percent."""


def read(run):
    rows = run.count("decode_steps") * int(run.deployment["slots"])
    return 100.0 * run.count("tokens") / rows if rows else None
