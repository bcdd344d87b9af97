"""The one traffic generator: every mix is a ``traffic/<mix>.json`` it reads.

A mix is a closed loop or an open one. A closed loop has ``clients``
callers, each sending its next request when its last one completes. An open
loop sends request k at its scheduled offset from the start, whether or not
earlier requests have finished. Keys (lengths in tokens, times in seconds):

- ``clients``: how many callers (a closed loop).
- ``arrivals``: the schedule of an open loop,
  ``{"dist": "gamma", "cv": c, "rate_rps": r}``: Gamma gaps between
  arrivals with coefficient of variation ``c`` (shape ``1 / c**2``) and
  mean exactly ``1 / r``. A mix has ``clients`` or ``arrivals``, not both.
- ``prompt_tokens``, ``output_tokens``: a lognormal length distribution,
  ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
  (clipped to ``[a, b]``).
- ``sizes``: how many (prompt, output) pairs one run cycles through.
- ``ramp_s``: seconds of load before the window opens.
- ``check_served_tokens``: served tokens the correctness check compares
  at the least.
- ``users``, ``source``: who sends such traffic, and where its numbers come
  from (read by no code).

Every seed gets the same lengths in the same order (quantiles of the
distributions, shuffled once by a fixed seed), the same first shares and
the same arrival schedule (gaps drawn the same way); the seed draws the
token ids. So two seeds do the same work in the same sequence at the same
instants, and request k is the same request whenever it is sent.
"""
from __future__ import annotations

import statistics

import numpy as np

ORDER_SEED = 0x5EED         # the one order of lengths every seed gets


def _seq(seed: int, *stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % (1 << 64), *stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a length distribution, as whole tokens."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of an arrival schedule's gaps, in seconds,
    scaled so that their mean is exactly ``1 / rate_rps``."""
    if arrivals["dist"] != "gamma":
        raise ValueError(f"unknown arrival distribution "
                         f"{arrivals['dist']!r}")
    from scipy import stats     # only open loops need it
    shape = 1.0 / float(arrivals["cv"]) ** 2
    x = stats.gamma.ppf((np.arange(n) + 0.5) / n, shape)
    return x / x.mean() / float(arrivals["rate_rps"])


class Stream:
    """Request k of a mix, for one seed: its prompt and its output length."""

    def __init__(self, mix: dict, seed: int, vocab: int, max_seq: int):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        n = int(mix["sizes"])
        order = np.random.default_rng(ORDER_SEED)
        self.prompt_lens = order.permutation(
            quantiles(mix["prompt_tokens"], n))
        self.output_lens = order.permutation(
            quantiles(mix["output_tokens"], n))
        if "arrivals" in mix:
            # request k is due at offsets[k % n] plus a whole number of
            # periods: the sizes' gaps, cycled
            g = order.permutation(gaps(mix["arrivals"], n))
            self.offsets = np.concatenate([[0.0], np.cumsum(g)[:-1]])
            self.period = float(g.sum())
        # never more tokens than the cache holds: prompt + output < max_seq
        self.prompt_lens = np.minimum(self.prompt_lens, max_seq - 2)
        self.max_seq = max_seq

    def request(self, k: int):
        """(prompt token ids, new tokens to generate) of request k."""
        i = k % len(self.prompt_lens)
        ids = np.random.default_rng(_seq(self.seed, 1, k)).integers(
            1, self.vocab, size=int(self.prompt_lens[i]), dtype=np.int32)
        new = min(int(self.output_lens[i]), self.max_seq - 1 - len(ids))
        return ids, new

    def due(self, k: int) -> float:
        """Seconds from the open loop's start until request k is due."""
        n = len(self.offsets)
        return (k // n) * self.period + float(self.offsets[k % n])

    def first_shares(self, clients: int) -> np.ndarray:
        """Share of its output each client's first request asks for."""
        return np.random.default_rng(ORDER_SEED).permutation(
            (np.arange(clients) + 0.5) / clients)

    def bounds(self) -> tuple:
        """(shortest, longest) prompt this stream can send."""
        return int(self.prompt_lens.min()), int(self.prompt_lens.max())
