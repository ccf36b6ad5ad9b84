import numpy as np

from bench.core import traffic as T

OPEN = {"arrivals": "open", "rate_rps": 8.0, "output_tokens": 16,
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                   "min": 64, "max": 2048}, "pre_window_s": 2.0}
CLOSED = dict(OPEN, arrivals="closed", outstanding=8)
FLOWS = np.arange(40).reshape(20, 2)
REJ = np.arange(20) % 4 == 0  # a quarter of the rows rejected


def gen(mix, seed, secs=10.0):
    return T.generate(mix, seed, secs, 1000, FLOWS, REJ)


def key(reqs):
    return [(r.phase, r.due, r.prompt.tolist(), r.feat.tolist(), r.reject)
            for r in reqs]


def test_same_seed_same_schedule():
    assert key(gen(OPEN, 5)) == key(gen(OPEN, 5))
    assert key(gen(CLOSED, 5)) == key(gen(CLOSED, 5))


def test_different_seeds_differ():
    assert key(gen(OPEN, 5)) != key(gen(OPEN, 6))
    assert key(gen(CLOSED, 2**33 + 1)) != key(gen(CLOSED, 2**33 + 2))


def test_arrivals_are_the_same_for_every_seed():
    a = [r.due for r in gen(OPEN, 5)]
    assert a == [r.due for r in gen(OPEN, 2**40 + 3)]
    assert len(set(len(r.prompt) for r in gen(OPEN, 5))) > 1


def test_window_carries_the_same_work_for_every_seed():
    for seed in (1, 2, 3**20):
        win = [r for r in gen(OPEN, seed) if r.phase == "win"]
        assert len(win) == 80  # 8 req/s x 10 s
        lens = sorted(len(r.prompt) for r in win if not r.reject)
        ref = sorted(len(r.prompt) for r in gen(OPEN, 1)
                     if r.phase == "win" and not r.reject)
        assert lens == ref
        dues = [r.due for r in win]
        assert dues[0] == 0.0 and dues == sorted(dues)


def test_closed_loop_strata_repeat():
    reqs = gen(CLOSED, 9)
    s = T.STRATUM
    a = sorted(len(r.prompt) for r in reqs[:s])
    b = sorted(len(r.prompt) for r in reqs[s:2 * s])
    assert a == b
    assert sum(r.reject for r in reqs[:s]) == round(s * REJ.mean())


def test_rejected_rows_match_the_verdict():
    for r in gen(OPEN, 3):
        row = int(np.flatnonzero((FLOWS == r.feat).all(1))[0])
        assert REJ[row] == r.reject


def test_gaps_sum_to_the_window():
    g = T.poisson_gaps(80, 8.0)
    assert np.isclose(g.sum(), 10.0)
    assert (g > 0).all()


def test_lengths_clip_and_quantiles():
    x = T.prompt_lengths(OPEN["prompt"], 23)
    assert x.min() >= 64 and x.max() <= 2048
    assert x[11] == 512  # the middle quantile is the median
    u = T.prompt_lengths({"dist": "uniform", "min": 2048, "max": 6144}, 4)
    assert u.tolist() == [2560, 3584, 4608, 5632]
