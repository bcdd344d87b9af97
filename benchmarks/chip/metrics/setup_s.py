"""Set-up: from process start until the window opens. Making the weights,
compiling or loading every program from the cache, the warm-up and the
ramp into steady load."""


def read(run):
    return run.setup_s
