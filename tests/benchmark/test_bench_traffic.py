"""The traffic generator: seed determinism, length bounds, the same work
for every seed, and the driver's due-time bookkeeping."""
import json
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench_paths import BENCH_DIR

from chipbench import traffic
from chipbench.load import Driver

MIXES = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 2 ** 33 + 7        # more than 32 bits


def mix(name):
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.Stream(mix(name), BIG_SEED, 64000, 2048)
    b = traffic.Stream(mix(name), BIG_SEED, 64000, 2048)
    for k in (0, 1, 17, 300):
        (pa, na), (pb, nb) = a.request(k), b.request(k)
        assert na == nb and np.array_equal(pa, pb)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_lengths(name):
    """The seed draws the token ids alone: request k has the same length,
    and each client's first request the same share, for every seed."""
    m = mix(name)
    streams = [traffic.Stream(m, seed, 64000, 2048)
               for seed in (1, 2, BIG_SEED)]
    for k in (0, 1, 17, 300):
        reqs = [s.request(k) for s in streams]
        assert len({(len(ids), new) for ids, new in reqs}) == 1
        assert not np.array_equal(reqs[0][0], reqs[1][0])
    shares = [s.first_shares(m["clients"]) for s in streams]
    assert all(np.array_equal(shares[0], x) for x in shares[1:])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_bounds(name):
    m = mix(name)
    s = traffic.Stream(m, 5, 64000, 2048)
    for k in range(int(m["sizes"])):
        ids, new = s.request(k)
        p, o = m["prompt_tokens"], m["output_tokens"]
        assert p["min"] <= len(ids) <= p["max"]
        assert o["min"] <= new <= o["max"]
        assert len(ids) + new < 2048
        assert ids.min() >= 1 and ids.max() < 64000


def test_quantiles_follow_each_distribution():
    ln = traffic.quantiles({"dist": "lognormal", "median": 384,
                            "sigma": 0.8, "min": 32, "max": 1536}, 1001)
    assert np.median(ln) == 384 and ln.min() >= 32 and ln.max() <= 1536
    assert np.all(np.diff(ln) >= 0)
    # mid-quantiles of a lognormal: the mean is median * exp(sigma^2 / 2)
    wide = traffic.quantiles({"dist": "lognormal", "median": 100,
                              "sigma": 0.5, "min": 1, "max": 10 ** 6}, 4001)
    assert wide.mean() == pytest.approx(100 * np.exp(0.125), rel=0.01)
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "uniform", "min": 1, "max": 2}, 3)


class FakeRequest:
    def __init__(self, tokens, max_new):
        self.tokens, self.generated = tokens, [0] * max_new
        self.future = Future()
        self.first_token_t = self.done_t = None


def test_closed_loop_times_each_request_from_its_release():
    """A client's next request is due when its last one completed; the
    number in flight stays at the client count."""
    m = {"clients": 3, "sizes": 8,
         "prompt_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                           "min": 4, "max": 8},
         "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.4,
                           "min": 2, "max": 6}}
    stream = traffic.Stream(m, 9, 100, 64)
    inflight, peak, lock = [0], [0], threading.Lock()

    def submit(tokens, max_new_tokens, eos_id):
        r = FakeRequest(tokens, max_new_tokens)
        with lock:
            inflight[0] += 1
            peak[0] = max(peak[0], inflight[0])

        def finish():
            time.sleep(0.002 * max_new_tokens)
            r.first_token_t = r.done_t = time.perf_counter()
            with lock:
                inflight[0] -= 1
            r.future.set_result(np.zeros(max_new_tokens))
        threading.Thread(target=finish, daemon=True).start()
        return r

    d = Driver(m, stream, submit).start(time.perf_counter())
    time.sleep(0.3)
    d.stop()
    assert d.drain(5)
    assert peak[0] == 3 and len(d.sent) > 20
    by_client = {}
    for s in d.sent:
        by_client.setdefault(s.client, []).append(s)
    for sents in by_client.values():
        for prev, nxt in zip(sents, sents[1:]):
            assert nxt.due == prev.request.done_t
            assert nxt.submit >= nxt.due
    assert [s.index for s in d.sent] == list(range(len(d.sent)))
    # staggered first requests ask for a share of their drawn output
    firsts = [s.max_new for s in d.sent[:3]]
    assert firsts != [stream.request(k)[1] for k in range(3)]
