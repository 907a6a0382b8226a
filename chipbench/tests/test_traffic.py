"""The general traffic generator: a pure function of (mix, seed)."""

import numpy as np
import pytest

from chipbench import spec, traffic

CHAT = spec.load_json(spec.find({"paths": ["chipbench"]}, "traffic",
                                "chat-open.json"))
DOCS = spec.load_json(spec.find({"paths": ["chipbench"]}, "traffic",
                                "docs-closed.json"))


def test_same_seed_same_inputs_other_seed_other_inputs():
    a = [traffic.request(CHAT, 7, i, 50257) for i in range(50)]
    b = [traffic.request(CHAT, 7, i, 50257) for i in range(50)]
    c = [traffic.request(CHAT, 8, i, 50257) for i in range(50)]
    assert all(np.array_equal(p, q) and m == n
               for (p, m), (q, n) in zip(a, b))
    assert any(len(p) != len(q) for (p, _), (q, _) in zip(a, c))
    assert np.array_equal(traffic.arrivals(CHAT, 7, 30),
                          traffic.arrivals(CHAT, 7, 30))


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["chat", "docs"])
def test_lengths_stay_inside_the_mix_and_the_slot(mix):
    cls = mix["classes"][0]
    lo, hi = traffic.prompt_range(mix)
    assert (lo, hi) == (cls["prompt_len"]["min"], cls["prompt_len"]["max"])
    drawn = [traffic.request(mix, 3, i, 50257) for i in range(400)]
    for prompt, n_out in drawn:
        assert lo <= len(prompt) <= hi
        assert cls["output_len"]["min"] <= n_out <= cls["output_len"]["max"]
        assert len(prompt) + n_out <= 1024        # no request can be refused
        assert prompt.min() >= 0 and prompt.max() < 50257
    assert len({len(p) for p, _ in drawn}) > 50    # a distribution, not a point


def test_chat_answers_have_the_stated_median():
    outs = [traffic.request(CHAT, 11, i, 50257)[1] for i in range(2000)]
    assert np.median(outs) == pytest.approx(160, rel=0.1)


@pytest.mark.parametrize("mix,within", [(CHAT, (0.10, 0.10)),
                                        (DOCS, (0.025, 0.05))],
                         ids=["chat", "docs"])
def test_every_seed_offers_the_same_amount_of_work(mix, within):
    """Quasi-random lengths: the mean over a window's worth of consecutive
    requests barely moves with the seed or with where the window starts."""
    def means(seed, start, n=120):
        drawn = [traffic.request(mix, seed, i, 50257)
                 for i in range(start, start + n)]
        return (np.mean([len(p) for p, _ in drawn]),
                np.mean([o for _, o in drawn]))
    got = np.array([means(seed, start) for seed in range(1, 9)
                    for start in (0, 37)])
    spread = (got.max(0) - got.min(0)) / got.mean(0)
    # independent draws would spread the docs mix's mean prompt by ~5% here
    assert spread[0] < within[0] and spread[1] < within[1]


def test_arrivals_are_a_fixed_rate_inside_the_window():
    mix = dict(CHAT, rate_per_s=50.0)
    t = traffic.arrivals(mix, 5, 40.0)
    assert np.all(np.diff(t) > 0) and t[0] > 0 and t[-1] < 40.0
    assert len(t) == pytest.approx(2000, rel=0.1)
    gaps = np.diff(t)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.15)   # Poisson
    bursty = np.diff(traffic.arrivals(dict(mix, arrival_cv=3.0), 5, 400.0))
    assert bursty.std() / bursty.mean() == pytest.approx(3.0, rel=0.25)


def test_classes_are_drawn_by_weight():
    mix = {"classes": [
        {"weight": 3, "prompt_len": {"dist": "fixed", "value": 10},
         "output_len": {"dist": "fixed", "value": 2}},
        {"weight": 1, "prompt_len": {"dist": "fixed", "value": 500},
         "output_len": {"dist": "fixed", "value": 4}}]}
    n_long = sum(len(traffic.request(mix, 1, i, 100)[0]) == 500
                 for i in range(2000))
    assert n_long == pytest.approx(500, rel=0.15)
    assert traffic.prompt_range(mix) == (10, 500)


def test_lm_batches_are_the_permutation_task():
    mix = {"seq_len": 16}
    it = traffic.lm_batches(mix, 9, 101, 4)
    (x0, y0), (x1, y1) = next(it), next(it)
    assert x0.shape == y0.shape == (4, 16) and x0.dtype == np.int32
    assert not np.array_equal(x0, x1)
    perm = {}
    for x, y in ((x0, y0), (x1, y1)):
        for a, b in zip(x.ravel(), y.ravel()):
            assert perm.setdefault(int(a), int(b)) == int(b)   # a function
    assert len(set(perm.values())) == len(perm)                # one to one
    again = next(traffic.lm_batches(mix, 9, 101, 4))
    assert np.array_equal(again[0], x0) and np.array_equal(again[1], y0)
