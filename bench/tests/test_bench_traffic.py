"""The traffic generator: a seed gives the same requests every time, every
seed gets the same sizes and gaps in another order, and the draws follow
the mix's parameters."""
import statistics

import numpy as np
import pytest

import bench_tiny_root  # noqa: F401  (puts the repo on sys.path)
from bench import gen

BURSTY = {"shape_seed": 0,
          "arrivals": {"kind": "gamma", "shape": 0.25, "rate_per_s": 3.0},
          "prompt_len": {"kind": "lognormal", "median": 256, "sigma": 1.0,
                         "min": 1, "max": 1024},
          "output_len": {"kind": "lognormal", "median": 24, "sigma": 0.8,
                         "min": 1, "max": 128}}
STEADY = dict(BURSTY, arrivals={"kind": "poisson", "rate_per_s": 2.0})


def _key(reqs):
    return [(r.rid, r.due, r.out_len, r.prompt.tobytes()) for r in reqs]


def test_same_seed_same_requests():
    seed = 2**31 + 123  # the driver's seeds pass 32 signed bits
    a = gen.requests(BURSTY, seed, 51, 151936)
    b = gen.requests(BURSTY, seed, 51, 151936)
    assert _key(a) == _key(b)


def test_seeds_share_sizes_and_arrivals():
    a = gen.requests(BURSTY, 1, 51, 1000)
    b = gen.requests(BURSTY, 2, 51, 1000)
    assert len(a) == len(b) == round(3.0 * 51)
    same = lambda r: (r.due, r.prompt.size, r.out_len)
    assert list(map(same, a)) == list(map(same, b))
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))


@pytest.mark.parametrize("mix,cv", [(BURSTY, 2.0), (STEADY, 1.0)])
def test_arrivals_follow_rate_and_burstiness(mix, cv):
    seconds = 4000.0
    reqs = gen.requests(mix, 7, seconds, 100)
    due = np.array([r.due for r in reqs])
    assert len(reqs) == round(mix["arrivals"]["rate_per_s"] * seconds)
    assert due[0] == 0.0 and due[-1] < seconds and np.all(np.diff(due) >= 0)
    g = np.diff(due)
    assert abs(g.std() / g.mean() - cv) < 0.15 * cv


def test_lengths_follow_lognormal_and_cap():
    reqs = gen.requests(BURSTY, 3, 4000.0, 100)
    plen = np.array([r.prompt.size for r in reqs])
    olen = np.array([r.out_len for r in reqs])
    assert abs(statistics.median(plen) / 256 - 1) < 0.06
    assert abs(statistics.median(olen) / 24 - 1) < 0.06
    assert plen.min() >= 1 and plen.max() == 1024
    assert olen.max() == 128
    # a capped share of a lognormal: P(X > cap) = 1 - Phi(ln(cap/med)/s)
    assert abs((plen == 1024).mean() - 0.0832) < 0.02
    sig = np.log(plen[(plen > 1) & (plen < 1024)]).std()
    assert 0.8 < sig < 1.0  # truncated at both ends, so under sigma 1


def test_batches_rows_all_differ_and_repeat_per_seed():
    mix = {"batch": {"pool": 8, "rows": 4, "seq": 512}}
    t, l = gen.batches(mix, 5, 151936)
    assert t.shape == l.shape == (8, 4, 512)
    rows = {r.tobytes() for r in t.reshape(-1, 512)}
    assert len(rows) == 32
    t2, l2 = gen.batches(mix, 5, 151936)
    assert (t == t2).all() and (l == l2).all()
