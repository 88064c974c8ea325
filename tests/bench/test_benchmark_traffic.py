"""The traffic generator and the metric arithmetic."""

import numpy as np
import pytest

from benchmarks import stats
from benchmarks import traffic as T

CHAT = {
    "rate_per_s": 4.0, "lead_s": 12.0,
    "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16, "max": 768},
    "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.6, "min": 16, "max": 256},
}
SEEDS = [0, 1, 7, 2**31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_count_of_arrivals_under_every_seed(seed):
    reqs = T.open_loop_requests(CHAT, seed, 40.0, 50257, tail_s=60.0)
    window = [r for r in reqs if 0 <= r.due_s < 40.0]
    lead = [r for r in reqs if r.due_s < 0]
    assert len(window) == 160 and len(lead) == 48 and len(reqs) == 160 + 48 + 240


def test_same_set_of_work_in_another_order():
    def sets(seed):
        w = [r for r in T.open_loop_requests(CHAT, seed, 40.0, 50257, 60.0)
             if 0 <= r.due_s < 40.0]
        gaps = np.diff([r.due_s for r in w])
        import collections
        return (sorted(len(r.prompt) for r in w),
                sorted(r.max_new_tokens for r in w),
                collections.Counter(np.round(gaps, 9)))

    a, b = sets(3), sets(4)
    assert a[0] == b[0] and a[1] == b[1]
    # the first arrival sits at 0, so each seed drops one gap of the set
    assert sum((a[2] - b[2]).values()) <= 1 and sum((b[2] - a[2]).values()) <= 1
    w3 = [len(r.prompt) for r in T.open_loop_requests(CHAT, 3, 40.0, 50257, 60.0)]
    w4 = [len(r.prompt) for r in T.open_loop_requests(CHAT, 4, 40.0, 50257, 60.0)]
    assert w3 != w4  # the order is the seed's


def test_same_seed_same_inputs():
    a = T.open_loop_requests(CHAT, 2**31 + 9, 10.0, 50257, 5.0)
    b = T.open_loop_requests(CHAT, 2**31 + 9, 10.0, 50257, 5.0)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               for x, y in zip(a, b))


def test_lengths_follow_the_mix():
    reqs = T.open_loop_requests(CHAT, 5, 40.0, 50257, 0.0)
    p = [len(r.prompt) for r in reqs if r.due_s >= 0]
    o = [r.max_new_tokens for r in reqs if r.due_s >= 0]
    assert 16 <= min(p) and max(p) <= 768 and 16 <= min(o) and max(o) <= 256
    assert np.median(p) == pytest.approx(192, rel=0.03)
    assert np.median(o) == pytest.approx(96, rel=0.03)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 50257 for r in reqs)


def test_arrivals_fill_the_span_in_order():
    t = T.arrival_times(4.0, 40.0, np.random.default_rng(0))
    assert t[0] == 0.0 and np.all(np.diff(t) > 0) and t[-1] < 40.0


def test_closed_loop_blocks_hold_the_same_lengths():
    mix = {"clients": 8, "prompt_len": {"dist": "uniform", "min": 512, "max": 896},
           "output_len": {"dist": "uniform", "min": 32, "max": 128}}
    gen = T.closed_loop_requests(mix, 1, 1000)
    one = [next(gen) for _ in range(8)]
    two = [next(gen) for _ in range(8)]
    assert sorted(len(r.prompt) for r in one) == sorted(len(r.prompt) for r in two)
    assert [r.index for r in one + two] == list(range(16))


def test_token_batches_rows_all_differ():
    x, y = next(T.token_batches(2**31 + 1, 8, 64, 50257))
    assert x.shape == y.shape == (8, 64) and np.array_equal(x[:, 1:], y[:, :-1])
    assert len({r.tobytes() for r in x}) == 8


@pytest.mark.parametrize("q,expected", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0)])
def test_percentile_interpolates(q, expected):
    assert stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(expected)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_tpot_is_last_minus_first_over_tokens_less_one():
    assert stats.tpot_s([1.0, 1.1, 1.2, 1.3]) == pytest.approx(0.1)
    assert stats.tpot_s([1.0]) is None


def test_latency_is_timed_from_the_due_time():
    due, sent, first = 10.0, 10.4, 10.5  # a stalled generator sent it late
    assert first - due == pytest.approx(0.5)  # what the runner records as TTFT
    assert sent - due == pytest.approx(0.4)  # and as the generator's lateness


def test_a_stall_in_the_window_moves_tpot_and_its_tail():
    tick = 0.1
    steady = [[i * tick for i in range(100)] for _ in range(40)]
    base = stats.percentile([stats.tpot_s(t) for t in steady], 95)
    stalled = [[(i * tick) + (1.0 if i >= 50 else 0.0) for i in range(100)]
               for _ in range(40)]
    moved = stats.percentile([stats.tpot_s(t) for t in stalled], 95)
    assert base == pytest.approx(0.1)
    assert moved == pytest.approx(0.1 + 1.0 / 99)  # one second over 99 gaps


def test_gaps_count_only_those_that_end_in_the_window():
    assert stats.gaps_s([0.0, 1.0, 2.5, 4.0], 1.0, 3.0) == pytest.approx([1.0, 1.5])


# -- the mixes as data --------------------------------------------------------

def _chat_file():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "traffic", "chat.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("key,published", [("prompt_len", 161.31), ("output_len", 337.99)])
def test_chat_lengths_return_the_published_means(key, published):
    """The traffic file names its source; unclipped, its log-normal has
    the mean that the source publishes, and the clip is GPT-2's 1,024
    positions shared between prompt and answer."""
    mix = _chat_file()
    assert "arXiv:2309.06180" in mix["lengths_source"]
    dist = dict(mix[key])
    assert dist.pop("min") >= 1 and dist.pop("max") == 512
    assert T.quantiles(dist, 200_000).mean() == pytest.approx(published, rel=0.01)
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 1024


def test_an_empirical_histogram_is_a_table_of_quantiles():
    dist = {"dist": "empirical", "quantiles": [10, 20, 40, 80, 160]}
    q = T.quantiles(dist, 4)  # at 1/8, 3/8, 5/8, 7/8
    assert list(q) == pytest.approx([15.0, 30.0, 60.0, 120.0])
    assert list(T.quantiles({**dist, "max": 100}, 4))[-1] == 100.0


def test_independent_draws_would_change_the_offered_work():
    """Why a draw is the quantiles shuffled: 320 independent draws of the
    chat mix's output lengths differ from seed to seed by some percent in
    their sum, the offered work; the shuffled quantiles by nothing."""
    dist = _chat_file()["output_len"]
    sums = [T.inverse_cdf(dist, np.random.default_rng(s).random(320)).sum()
            for s in range(40)]
    assert (max(sums) - min(sums)) / np.mean(sums) > 0.05
    same = {int(T.draw_lengths(dist, 320, np.random.default_rng(s)).sum()) for s in range(5)}
    assert len(same) == 1
