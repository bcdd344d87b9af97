"""Router: how far the busiest replica's share of the window's generated
tokens lies above the mean replica's, in percent: 100 x (max / mean - 1),
from each engine's ``tokens`` counter. 0 is an even spread; with one
replica there is nothing to route and nothing to read."""


def read(run):
    tokens = run.count_per_replica("tokens")
    if len(tokens) < 2 or not sum(tokens):
        return None
    return 100.0 * (max(tokens) * len(tokens) / sum(tokens) - 1.0)
