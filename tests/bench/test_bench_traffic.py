"""Traffic generators: deterministic from the seed, with the stated
distributions, rate and amount of work."""
import math

import numpy as np
import pytest

from bench import common

ol = common.load_module("traffic", "open_loop")
ls = common.load_module("traffic", "layer_stream")


def _mix(name):
    return common.load_json("traffic", name)


@pytest.mark.parametrize("mix", ["chat", "longprompt"])
def test_open_loop_deterministic_from_seed(mix):
    m = _mix(mix)
    a = ol.generate(m, rate=3.0, seconds=20, seed=2**31 + 99, vocab=1000)
    b = ol.generate(m, rate=3.0, seconds=20, seed=2**31 + 99, vocab=1000)
    c = ol.generate(m, rate=3.0, seconds=20, seed=2**31 + 100, vocab=1000)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert [r["prompt"].size for r in a] != [r["prompt"].size for r in c]


@pytest.mark.parametrize("mix", ["chat", "longprompt"])
def test_every_seed_gets_the_same_work(mix):
    m = _mix(mix)
    runs = [ol.generate(m, rate=2.5, seconds=40, seed=s, vocab=50) for s in (1, 2, 3)]
    for key in ("max_new",):
        assert len({tuple(sorted(r[key] for r in run)) for run in runs}) == 1
    assert len({tuple(sorted(r["prompt"].size for r in run)) for run in runs}) == 1
    assert len({round(run[-1]["due"], 9) for run in runs}) == 1  # same gaps, reordered


@pytest.mark.parametrize("n", [49, 50, 10, 7, 3])
def test_block_order_takes_one_from_each_stratum(n):
    k = 7
    m = -(-n // k)
    sizes = [max(0, min(m, n - j * m)) for j in range(k)]
    groups = [sum(z > t for z in sizes) for t in range(m)]
    for seed in (1, 2**31 + 7):
        order = ol.order(np.random.default_rng(seed), n, k)
        assert sorted(order) == list(range(n))
        at = 0
        for size in groups:
            strata = [int(r) // m for r in order[at:at + size]]
            assert len(set(strata)) == size
            at += size
    if n % k == 0:
        assert groups == [k] * m


def test_chat_blocks_hold_the_whole_distribution():
    m = _mix("chat")
    k = m["block"]
    base = ol.lengths(m["prompt"], 49)
    for seed in (2**31 + 1, 2**31 + 2):
        reqs = ol.generate(m, rate=0.96, seconds=51, seed=seed, vocab=50)
        assert len(reqs) == 49
        for b in range(7):
            p = sorted(r["prompt"].size for r in reqs[b * k:(b + 1) * k])
            # one prompt from each seventh of the sorted multiset
            assert all(base[7 * j] <= p[j] <= base[7 * j + 6] for j in range(7))
            gaps = np.diff([0.0] + [r["due"] for r in reqs])[b * k:(b + 1) * k]
            assert 0.5 * k / 0.96 < gaps.sum() < 1.6 * k / 0.96


def test_chat_lengths_match_the_stated_distribution():
    m = _mix("chat")
    reqs = ol.generate(m, rate=10.0, seconds=100, seed=7, vocab=50)
    p = np.array([r["prompt"].size for r in reqs])
    o = np.array([r["max_new"] for r in reqs])
    assert p.min() >= 32 and p.max() <= 2048 and abs(np.median(p) - 256) <= 3
    assert o.min() >= 8 and o.max() <= 512 and abs(np.median(o) - 64) <= 1
    # sigma 1.0 of the log: the 84th percentile sits one e-fold above the median
    assert abs(np.log(np.percentile(p, 84.13) / 256) - 1.0) < 0.05
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 50 for r in reqs)


def test_longprompt_lengths_match_the_stated_distribution():
    m = _mix("longprompt")
    reqs = ol.generate(m, rate=4.0, seconds=100, seed=7, vocab=50)
    p = np.array([r["prompt"].size for r in reqs])
    o = np.array([r["max_new"] for r in reqs])
    assert p.min() >= 1024 and p.max() <= 4096
    assert abs(np.median(p) - 2048) <= 10  # log-uniform: median at the geometric mean
    assert o.min() == 16 and o.max() == 64 and abs(o.mean() - 40) < 0.5


def test_open_loop_rate_and_window():
    m = _mix("chat")
    reqs = ol.generate(m, rate=4.0, seconds=50, seed=3, vocab=50)
    due = np.array([r["due"] for r in reqs])
    assert len(reqs) == 200
    assert np.all(np.diff(due) >= 0) and due[0] > 0 and due[-1] <= 50
    assert abs(np.mean(np.diff(due)) - 0.25) < 0.01


def test_layer_stream_deterministic_and_drifting():
    model = {"d_model": 128, "d_ff": 256, "head_dim": 32, "n_heads": 4, "n_kv_heads": 2,
             "n_layers": 3}
    a = ls.tensor(5, model, 0.05, 0, 1, "mlp/wo")
    b = ls.tensor(5, model, 0.05, 0, 1, "mlp/wo")
    c = ls.tensor(5, model, 0.05, 1, 1, "mlp/wo")
    assert a.shape == (256, 128) and np.array_equal(np.asarray(a), np.asarray(b))
    drift = np.asarray(c - a) * math.sqrt(256)
    assert 0.03 < drift.std() < 0.07  # one checkpoint of drift 0.05 at fan-in scale
    assert list(ls.matrices(model)) == sorted(ls.matrices(model))
    order = ls.order(model)
    assert [next(order) for _ in range(4)] == [(0, 0), (0, 1), (0, 2), (1, 0)]
