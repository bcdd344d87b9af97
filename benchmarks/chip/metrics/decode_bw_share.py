"""Model step: the bytes the window's decode steps need, over what the HBM
of the chips the cell used could move in the window, in percent. Each step
(of any replica) reads every weight of its replica once; each generated
token reads its request's cached keys and values at its real context.
Counted from the shapes by the family's cost model."""


def read(run):
    if run.peaks is None:
        return None
    steps = run.count("decode_steps")
    if not steps:
        return None
    cost, model = run.cost, run.model
    _, ctx = run.token_contexts()
    need = steps * cost.param_bytes(model) + \
        ctx * cost.kv_bytes_per_position(model)
    return 100.0 * need / (run.peaks.hbm_bw * run.chips * run.window_s)
