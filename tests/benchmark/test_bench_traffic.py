"""The traffic generator: seed determinism, length bounds, the same work
and the same arrival schedule for every seed, and the driver's due-time
bookkeeping in a closed and an open loop."""
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
OPEN_MIXES = [m for m in MIXES if "arrivals" in json.loads(
    (BENCH_DIR / "traffic" / f"{m}.json").read_text())]
# an open mix at a cell's size, beside the benchmark's own open mixes
GAMMA_CV2 = {"arrivals": {"dist": "gamma", "cv": 2.0, "rate_rps": 4.2},
             "sizes": 1024,
             "prompt_tokens": {"dist": "lognormal", "median": 384,
                               "sigma": 0.8, "min": 32, "max": 1536},
             "output_tokens": {"dist": "lognormal", "median": 128,
                               "sigma": 0.8, "min": 8, "max": 511}}
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
    and each client's first request the same share (a closed loop) or
    request k the same due time (an open loop), for every seed."""
    m = mix(name)
    streams = [traffic.Stream(m, seed, 64000, 2048)
               for seed in (1, 2, BIG_SEED)]
    for k in (0, 1, 17, 300):
        reqs = [s.request(k) for s in streams]
        assert len({(len(ids), new) for ids, new in reqs}) == 1
        assert not np.array_equal(reqs[0][0], reqs[1][0])
    if "clients" in m:
        shares = [s.first_shares(m["clients"]) for s in streams]
        assert all(np.array_equal(shares[0], x) for x in shares[1:])
    else:
        for k in (0, 1, 17, 300, 5000):
            assert len({s.due(k) for s in streams}) == 1


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


@pytest.mark.parametrize("name", OPEN_MIXES + ["gamma_cv2"])
def test_open_loop_schedule_has_its_rate_and_burstiness(name):
    """One schedule for every seed, whose gaps have a mean of exactly
    1 / rate and a coefficient of variation near the mix's; it repeats
    after ``sizes`` requests."""
    m = GAMMA_CV2 if name == "gamma_cv2" else mix(name)
    a, b = (traffic.Stream(m, seed, 64000, 2048) for seed in (3, BIG_SEED))
    n = int(m["sizes"])
    due = np.array([a.due(k) for k in range(2 * n + 1)])
    assert np.array_equal(due, [b.due(k) for k in range(2 * n + 1)])
    gaps = np.diff(due)
    rate, cv = m["arrivals"]["rate_rps"], m["arrivals"]["cv"]
    assert due[0] == 0.0 and np.all(gaps >= 0)
    assert gaps[:n].mean() == pytest.approx(1.0 / rate, rel=1e-9)
    assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.05)
    assert np.allclose(gaps[:n], gaps[n:])
    with pytest.raises(ValueError):
        traffic.gaps({"dist": "poisson", "rate_rps": 1.0}, 8)


def test_open_loop_sends_each_request_when_it_is_due():
    """Request k is due at t0 plus its scheduled offset, whatever the
    server does (here it never finishes anything), and is sent then."""
    m = {"arrivals": {"dist": "gamma", "cv": 2.0, "rate_rps": 40.0},
         "sizes": 16,
         "prompt_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                           "min": 4, "max": 8},
         "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.4,
                           "min": 2, "max": 6}}
    stream = traffic.Stream(m, BIG_SEED, 100, 64)
    got = []

    def submit(tokens, max_new_tokens, eos_id):
        got.append((time.perf_counter(), len(tokens), max_new_tokens))
        return FakeRequest(tokens, max_new_tokens)

    t0 = time.perf_counter() + 0.05
    d = Driver(m, stream, submit).start(t0)
    time.sleep(0.75)
    d.stop()
    sent = d.sent
    assert len(sent) == len(got) >= 20
    assert [s.index for s in sent] == list(range(len(sent)))
    for s, (t, plen, new) in zip(sent, got):
        assert s.client == -1
        assert s.due == t0 + stream.due(s.index)
        assert s.due <= s.submit <= t + 1e-3
        assert (plen, new) == (len(stream.request(s.index)[0]),
                               stream.request(s.index)[1])
    # the sender kept up (a busy host may delay a send now and then)
    assert np.median([s.submit - s.due for s in sent]) < 0.01
    # none finished, yet requests kept coming: the loop is open
    assert not any(s.done for s in sent)
    assert sent[-1].due <= time.perf_counter()
