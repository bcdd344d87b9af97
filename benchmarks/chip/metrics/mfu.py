"""Model step: the operations the window's work needs, over what the chip
could do in the window at its bf16 peak, in percent.

The work: the prompt of every request whose first token came in the window
(causal attention, the head at the last position) and every generated
token after a first in the window, at its real context (see
``RunRecord.token_contexts``), with its head. Counted from the shapes by the
family's cost model, never from what the program computes."""


def read(run):
    if run.peaks is None:
        return None
    cost, model = run.cost, run.model
    flops = sum(cost.prefill_flops(model, len(s.prompt))
                for s in run.first_token_in_window())
    n, ctx = run.token_contexts()
    flops += n * cost.decode_flops(model, 0.0) + \
        cost.attention_flops(model, ctx)
    peak = run.peaks.flops_bf16 * run.window_s
    return 100.0 * flops / peak if flops else None
