"""95th percentile, over every request whose first token came in the
window, of its time per output token after the first:
(last token - first token) / (tokens - 1)."""
from chipbench.window import percentile


def read(run):
    per = []
    for s in run.first_token_in_window():
        r = s.request
        if r.done_t is not None and len(r.generated) > 1:
            per.append((r.done_t - r.first_token_t)
                       / (len(r.generated) - 1) * 1e3)
    return percentile(per, 95)
