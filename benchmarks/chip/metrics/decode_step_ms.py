"""Decode loop: the window's length over the decode steps each engine made
in it (each step also runs any prefill or chunk the step admits). Every
replica runs its own loop on its own chip, so the steps are counted per
replica: the window over their mean."""


def read(run):
    steps = run.count("decode_steps")
    return run.window_s * 1e3 * run.chips / steps if steps else None
