"""Generated tokens per second: the engines' ``tokens`` counter over the
window, divided by the window's length."""


def read(run):
    return run.count("tokens") / run.window_s
