"""Decode loop: the window's length over the decode steps the engine made
in it (each step also runs any prefill or chunk the step admits)."""


def read(run):
    steps = run.count("decode_steps")
    return run.window_s * 1e3 / steps if steps else None
