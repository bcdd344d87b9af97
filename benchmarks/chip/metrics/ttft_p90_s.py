"""90th percentile, over every request due in the window, of its first
token's time less the time it was due: an open loop's request is due at
its scheduled arrival, a closed loop's when the completion that released
its client came. A request that failed or
never gave a token counts as infinitely late; when the percentile falls on
one, the metric is left out and the check's failure counts say why."""
import math

from chipbench.window import percentile


def read(run):
    vals = []
    for s in run.due_in_window():
        r = s.request
        ok = r is not None and not s.failed and r.first_token_t is not None
        vals.append(r.first_token_t - s.due if ok else math.inf)
    p = percentile(vals, 90)
    return p if p is not None and math.isfinite(p) else None
