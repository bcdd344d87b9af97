"""Whether what the timed path served is correct.

Once the window has closed, the program's state freed and the peak memory
read, a sample of the requests due in the window that the served path
finished, drawn from the seed and always holding the longest, is run
through the family's plain float32 reference, once over each prompt and
its served tokens. Each served token was the program's greedy choice; the
number compared is the widest gap by which a served token's reference
logit lies below the reference's best at that position.

Beside it, as counts with the limit 0: requests due in the window that
failed, that never finished, and sampled ones that served another number of
tokens than they asked for (the mixes ignore end-of-sequence).
"""
from __future__ import annotations

import numpy as np

MIN_SAMPLE = 3


def sample(sent_in_window, seed: int, served_tokens: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until ``served_tokens`` tokens and ``MIN_SAMPLE`` requests
    are in (or every finished one is)."""
    done = [s for s in sent_in_window
            if not s.failed and s.request is not None
            and s.request.done_t is not None]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.prompt)
                                       + len(s.request.generated), s.index))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([int(seed) % (1 << 64), 5])
    order = rng.permutation(len(rest)) if rest else []
    out = [longest]
    for i in order:
        if (sum(len(s.request.generated) for s in out) >= served_tokens
                and len(out) >= MIN_SAMPLE):
            break
        out.append(rest[i])
    return out


def gaps(logits: np.ndarray, chosen) -> np.ndarray:
    """Per position: the best logit minus the chosen token's."""
    chosen = np.asarray(chosen)
    return logits.max(axis=1) - logits[np.arange(len(chosen)), chosen]


def reference_gaps(ref, model: dict, seed: int, picked,
                   modes=("f32",)) -> dict:
    """mode -> per-request gap arrays. For "f32", the gap of each served
    token; for a lower-precision mode, the float32 gap of the token that
    mode puts first."""
    fed, pos = zip(*(ref.fed_and_positions(s.prompt, s.request.generated)
                     for s in picked))
    want = tuple(dict.fromkeys(("f32",) + tuple(modes)))
    out = ref.logits_at(model, seed, fed, pos, modes=want)
    res = {}
    for mode in modes:
        res[mode] = []
        for j, s in enumerate(picked):
            f32 = out["f32"][j]
            chosen = s.request.generated if mode == "f32" \
                else out[mode][j].argmax(axis=1)
            res[mode].append(gaps(f32, chosen))
    return res


def compare(checks: dict) -> bool:
    """Each check holds a value and a ``max`` or a ``min`` it must keep."""
    return all(c["value"] <= c.get("max", c["value"])
               and c["value"] >= c.get("min", c["value"])
               for c in checks.values())


def checks(run, ref, config: dict, seed: int, served_tokens: int,
           modes=("f32",)) -> dict:
    """mode -> the numbers compared, each with its limit. Mode "f32" judges
    the served tokens. A lower-precision mode is the control: in the served
    tokens' place it puts, at each position of the same prompts and tokens,
    the token that mode's reference puts first, and judges those."""
    due = run.due_in_window()
    picked = sample(due, seed, served_tokens)
    counts = {
        "failed_requests": {"value": sum(s.failed for s in due), "max": 0},
        "unfinished_requests": {"value": sum(not s.done for s in due),
                                "max": 0},
        "short_outputs": {"value": sum(len(s.request.generated) != s.max_new
                                       for s in picked), "max": 0},
        "checked_requests": {"value": len(picked), "min": MIN_SAMPLE},
    }
    g = reference_gaps(ref, config["model"], seed, picked, modes) \
        if picked else {}
    out = {}
    for mode in modes:
        out[mode] = dict(counts)
        if picked:
            out[mode]["checked_tokens"] = {
                "value": sum(len(x) for x in g[mode]), "min": served_tokens}
            out[mode]["logit_gap"] = {
                "value": float(max(x.max() for x in g[mode])),
                "max": float(config["check"]["logit_gap_limit"])}
    return out
