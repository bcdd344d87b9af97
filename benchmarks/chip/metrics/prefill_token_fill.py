"""Admission: the real prompt positions among the token positions that the
prefill programs computed in the window, in percent, from the engine's own
counters (``prefill_positions_real`` over ``prefill_positions_computed``).
A padded prefill computes its rows times the padded length, a chunk step
its rows times the chunk length."""


def read(run):
    computed = run.count("prefill_positions_computed")
    if not computed:
        return None
    return 100.0 * run.count("prefill_positions_real") / computed
