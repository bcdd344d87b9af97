"""What a run measured in its window, as the metric readers see it.

A reader (``metrics/<name>.py``) is a function ``read(run)`` of one
``RunRecord``; it returns a number, or None when the run has nothing it can
read, and the harness then leaves that metric out of the line.

Counters are the engines' cumulative ``metrics``, read at the window's open
and close; a window's count is their difference. A tail is the tail of all
requests the definition selects, failed ones included where the metric
says so.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default method); None when
    empty, inf when the rank touches an infinite (missing) value."""
    v = np.sort(np.asarray(list(values), np.float64))
    if not len(v):
        return None
    rank = q / 100.0 * (len(v) - 1)
    lo, hi = int(np.floor(rank)), int(np.ceil(rank))
    if not (np.isfinite(v[lo]) and np.isfinite(v[hi])):
        return math.inf
    return float(v[lo] + (rank - lo) * (v[hi] - v[lo]))


def counters(engines) -> dict:
    out: dict = {}
    for e in engines:
        for k, v in e.metrics.items():
            out[k] = out.get(k, 0) + v
    return out


@dataclasses.dataclass
class RunRecord:
    cell: str
    seconds: float              # the window's length as requested
    t_open: float
    t_close: float
    setup_s: float
    counters_open: dict
    counters_close: dict
    sent: list                  # every load.Sent of the run, ramp included
    model: dict                 # the configuration's "model"
    deployment: dict
    cost: object                # the family's cost module
    peaks: object               # device.Peaks of the chip
    device: dict                # the result's device record
    traced: bool = False
    profile: Optional[dict] = None   # profile.summarize() of the trace

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def count(self, name: str) -> int:
        return self.counters_close.get(name, 0) - \
            self.counters_open.get(name, 0)

    def inside(self, t) -> bool:
        return t is not None and self.t_open <= t < self.t_close

    def due_in_window(self) -> List:
        return [s for s in self.sent if self.inside(s.due)]

    def first_token_in_window(self) -> List:
        return [s for s in self.sent
                if s.request is not None and not s.failed
                and self.inside(s.request.first_token_t)]

    def token_contexts(self) -> tuple:
        """(generated tokens after a request's first, sum of their
        contexts) that fall inside the window. The engine stamps only a
        request's first and last token, so the others are placed evenly
        between the two; a token at step j of a prompt of P attends P + j
        positions."""
        n_tok, ctx = 0, 0.0
        for s in self.sent:
            r = s.request
            if r is None or s.failed or r.first_token_t is None:
                continue
            n = len(r.generated)
            if n < 2:
                continue
            end = r.done_t if r.done_t is not None else self.t_close
            j = np.arange(1, n)
            t = r.first_token_t + j * (end - r.first_token_t) / (n - 1)
            mask = (t >= self.t_open) & (t < self.t_close)
            n_tok += int(mask.sum())
            ctx += float((len(s.prompt) + j[mask]).sum())
        return n_tok, ctx

    def spans(self, name: str) -> List:
        """Every ``name`` span of every traced request: a list of dicts
        (``start``, ``end`` on the host clock, ``attrs``, ``events``)."""
        out = []
        for s in self.sent:
            tr = getattr(s.request, "trace", None)
            root = getattr(tr, "root", None)
            if root is None:
                continue
            stack = [root]
            while stack:
                sp = stack.pop()
                stack.extend(sp.children)
                if sp.name == name:
                    out.append({"start": sp.t0, "end": sp.t1,
                                "attrs": dict(sp.attrs),
                                "events": list(sp.events), "sent": s})
        return out

