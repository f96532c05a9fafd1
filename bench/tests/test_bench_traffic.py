"""The traffic generator: deterministic per seed, the same work for every
seed, and its draws match the mix's parameters."""
import numpy as np
import pytest

from bench import traffic

CHAT = {"kind": "open_loop", "pool_seed": 5, "ramp_s": 2,
        "arrivals": {"process": "poisson", "rate_per_s": 20.0},
        "shared_prefix": {"count": 4, "tokens": 64},
        "prompt": {"dist": "lognormal", "median": 100, "sigma": 0.9,
                   "min": 16, "max": 300},
        "output": {"dist": "lognormal", "median": 30, "sigma": 0.9,
                   "min": 4, "max": 90},
        "pool": 10000}
BURSTY = dict(CHAT, arrivals={"process": "gamma", "cv": 2.0,
                              "rate_per_s": 50.0}, pool=30000)
BURSTY.pop("shared_prefix")
BACKLOG = {"kind": "backlog", "pool": 300, "pool_seed": 9, "ramp_s": 1,
           "backlog_per_slot": 2, "prompt": CHAT["prompt"],
           "output": CHAT["output"]}


def _key(specs):
    return [(s.due, tuple(s.prompt.tolist()), s.max_new, s.prefix)
            for s in specs]


@pytest.mark.parametrize("mix", [CHAT, BURSTY, BACKLOG],
                         ids=["poisson", "gamma", "backlog"])
def test_same_seed_same_requests(mix):
    a = traffic.generate(mix, 2**31 + 17, seconds=20, vocab=1000)
    b = traffic.generate(mix, 2**31 + 17, seconds=20, vocab=1000)
    c = traffic.generate(mix, 3, seconds=20, vocab=1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [CHAT, BURSTY, BACKLOG],
                         ids=["poisson", "gamma", "backlog"])
def test_every_seed_gets_the_same_work(mix):
    """The schedule is the mix's; the seed draws only the token ids."""
    runs = [traffic.generate(mix, s, seconds=20, vocab=1000)
            for s in (1, 2, 2**33)]
    sched = [[(s.due, len(s.prompt), s.max_new, s.prefix) for s in r]
             for r in runs]
    assert sched[0] == sched[1] == sched[2]
    if mix["kind"] == "open_loop":
        span = mix["ramp_s"] + 20
        for r in runs:
            assert r[0].due == 0.0
            assert all(a.due <= b.due for a, b in zip(r, r[1:]))
            assert r[-1].due <= span


def test_lengths_are_clipped_and_centred():
    specs = traffic.generate(BURSTY, 4, seconds=200, vocab=1000)
    plen = np.array([len(s.prompt) for s in specs])
    olen = np.array([s.max_new for s in specs])
    assert plen.min() >= 16 and plen.max() <= 300
    assert olen.min() >= 4 and olen.max() <= 90
    assert abs(np.median(plen) - 100) < 10
    assert abs(np.median(olen) - 30) < 4
    # the clip holds the tail the lognormal would put beyond it
    assert (plen == 300).any() and (olen == 4).any()


def test_shared_prefix_share():
    specs = traffic.generate(CHAT, 6, seconds=100, vocab=1000)
    heads = {}
    for s in specs:
        assert 0 <= s.prefix < 4 and s.shared == 64
        heads.setdefault(s.prefix, set()).add(tuple(s.prompt[:64].tolist()))
    assert all(len(h) == 1 for h in heads.values())
    # the share of prompt tokens that are shared: 64 of each prompt
    own = np.array([len(s.prompt) - 64 for s in specs])
    share = 64 * len(specs) / (own.sum() + 64 * len(specs))
    assert 0.25 < share < 0.5
    assert all(s.shared == 0 and s.prefix < 0 for s in
               traffic.generate(BURSTY, 6, seconds=10, vocab=1000))


@pytest.mark.parametrize("mix,cv", [(BURSTY, 2.0), (CHAT, 1.0)],
                         ids=["gamma", "poisson"])
def test_arrival_rate_and_burstiness(mix, cv):
    specs = traffic.generate(mix, 8, seconds=400, vocab=10)
    gaps = np.diff([s.due for s in specs])
    rate = mix["arrivals"]["rate_per_s"]
    assert np.mean(gaps) == pytest.approx(1 / rate, rel=0.1)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(cv, rel=0.15)


def test_rate_override_scales_the_clock():
    # at twice the rate, a window of 10 s holds the arrivals that 2 x 12 s
    # hold at the mix's own rate, at half their gaps
    slow = traffic.generate(CHAT, 1, seconds=22, vocab=1000)
    fast = traffic.generate(CHAT, 1, seconds=10, vocab=1000, rate=40.0)
    assert len(fast) == len(slow)
    assert sorted(np.diff([s.due for s in fast])) == pytest.approx(
        sorted(0.5 * np.diff([s.due for s in slow])))


@pytest.mark.parametrize("mix", [CHAT, BURSTY], ids=["poisson", "gamma"])
def test_lengths_do_not_depend_on_window_or_rate(mix):
    """A sweep's rates and a shorter window serve a prefix of the cell's
    own requests: the k-th request's lengths and prefix stay put."""
    runs = [traffic.generate(mix, 1, seconds=20, vocab=1000),
            traffic.generate(mix, 1, seconds=7, vocab=1000),
            traffic.generate(mix, 1, seconds=20, vocab=1000, rate=3.0),
            traffic.generate(mix, 1, seconds=20, vocab=1000, rate=90.0)]
    sched = [[(len(s.prompt), s.max_new, s.prefix) for s in r] for r in runs]
    k = min(map(len, sched))
    assert k > 20 and all(x[:k] == sched[0][:k] for x in sched)


def test_more_arrivals_than_the_pool_are_refused():
    with pytest.raises(ValueError, match="pool"):
        traffic.generate(dict(CHAT, pool=50), 1, seconds=20, vocab=1000)
