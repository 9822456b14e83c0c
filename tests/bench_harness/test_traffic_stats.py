"""The traffic generator repeats from a seed and gives every seed the same
work; percentiles, failures and lateness are counted as the contract says."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks import lm_data, stats, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mix(name):
    return json.load(open(os.path.join(REPO, "benchmarks", "traffic", name + ".json")))


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 33 + 1])
def test_open_loop_repeats_byte_for_byte(seed):
    a = traffic.open_loop(_mix("chat-open"), seed, 30.0, 32768, ramp_s=40.0)
    b = traffic.open_loop(_mix("chat-open"), seed, 30.0, 32768, ramp_s=40.0)
    assert a == b
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


def test_seeds_differ_in_order_and_tokens_not_in_work():
    mix = _mix("chat-open")
    a = [r for r in traffic.open_loop(mix, 1, 30.0, 32768) if r.counted]
    b = [r for r in traffic.open_loop(mix, 2, 30.0, 32768) if r.counted]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.new_tokens for r in a) == sorted(r.new_tokens for r in b)
    gaps = lambda rs: sorted(np.round(np.diff([r.due_s for r in rs]), 9))
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30)


def test_lengths_follow_the_mix():
    mix = dict(_mix("chat-open"), rate_per_s=4.5)  # 270 requests: enough to reach the clip
    reqs = [r for r in traffic.open_loop(mix, 3, 60.0, 32768) if r.counted]
    lens = np.array([len(r.prompt) for r in reqs])
    assert lens.min() >= 64 and lens.max() == 2048  # clipped Pareto: a mass at the clip
    assert 150 < lens.mean() < 200
    news = np.array([r.new_tokens for r in reqs])
    assert news.min() >= 32 and news.max() <= 256 and abs(news.mean() - 144) < 2
    assert all(0 < t < 32768 for r in reqs[:5] for t in r.prompt)
    assert all(0.0 <= r.due_s < 60.0 for r in reqs)


def test_ramp_and_tail_are_sent_but_not_counted():
    plan = traffic.open_loop(_mix("chat-open"), 5, 30.0, 32768, ramp_s=40.0)
    ramp = [r for r in plan if r.due_s < 0]
    tail = [r for r in plan if r.due_s >= 30.0]
    assert ramp and tail and not any(r.counted for r in ramp + tail)
    assert all(r.counted for r in plan if 0 <= r.due_s < 30.0)
    assert len({r.index for r in plan}) == len(plan)


def test_bursty_arrivals_keep_the_mean_rate_and_bunch_up():
    mix = dict(_mix("chat-open"), arrivals={"kind": "bursty", "factor": 4, "burst_s": 2, "period_s": 10})
    due = traffic.due_times(mix, 9, 60.0, 1)
    assert len(due) == round(mix["rate_per_s"] * 60)
    in_burst = np.mean((due % 10) < 2)
    assert 0.4 < in_burst < 0.6  # 4x the quiet rate for a fifth of the time: half the requests


def test_closed_loop_list_repeats_and_keeps_the_multiset():
    mix = _mix("batch-closed")
    a, b = traffic.closed_loop(mix, 4, 32000), traffic.closed_loop(mix, 4, 32000)
    assert a == b and len(a) == mix["request_list"]
    assert len({r.prompt for r in a}) == len(a)  # no prompt twice: nothing for a prefix cache
    whole = dict(mix, block=0, stagger_first=0)
    lens = lambda plan: sorted(len(r.prompt) for r in plan)
    assert lens(a) == lens(traffic.closed_loop(whole, 4, 32000))
    assert max(lens(a)) == 2048  # dealing into blocks keeps the clipped tail


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 11])
def test_every_block_of_a_closed_loop_list_carries_the_same_tokens(seed):
    mix = _mix("batch-closed")
    k, block = mix["stagger_first"], mix["block"]
    news = np.array([r.new_tokens for r in traffic.closed_loop(mix, seed, 32000)])
    sums = news[k:].reshape(-1, block).sum(axis=1)  # past the staggered first wave
    assert (sums.max() - sums.min()) / sums.mean() < 0.02
    free = np.array([r.new_tokens for r in traffic.closed_loop(dict(mix, block=0), seed, 32000)])
    loose = free[k:].reshape(-1, block).sum(axis=1)
    assert (loose.max() - loose.min()) / loose.mean() > 0.1


def test_first_wave_of_a_closed_loop_is_cut_to_spread_fractions():
    mix = _mix("batch-closed")
    k = mix["stagger_first"]
    cut = traffic.closed_loop(mix, 6, 32000)
    full = traffic.closed_loop(dict(mix, stagger_first=0), 6, 32000)
    share = np.array([c.new_tokens / f.new_tokens for c, f in zip(cut[:k], full[:k])])
    assert cut[k:] == full[k:] and all(c.prompt == f.prompt for c, f in zip(cut, full))
    assert np.allclose(np.sort(share), (np.arange(k) + 0.5) / k, atol=0.02)
    assert min(r.new_tokens for r in cut) >= 1


def test_a_list_that_is_not_whole_blocks_is_an_error():
    with pytest.raises(ValueError, match="whole blocks"):
        traffic.closed_loop(dict(_mix("batch-closed"), request_list=100), 1, 32000)


@pytest.mark.parametrize("spec,n", [({"dist": "uniform", "min": 5, "max": 5}, 4),
                                    ({"dist": "uniform", "min": 2, "max": 3}, 10)])
def test_quantiles_of_simple_distributions(spec, n):
    q = traffic.quantiles(spec, n)
    assert len(q) == n and q.min() >= spec["min"] and q.max() <= spec["max"]


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf"}, 3)


def test_training_rows_repeat_and_all_differ():
    a, b = lm_data.rows(2 ** 31 + 9, 600, 64, 512), lm_data.rows(2 ** 31 + 9, 600, 64, 512)
    assert a.dtype == np.int32 and (a == b).all()
    assert len({r.tobytes() for r in a}) == 600
    assert (lm_data.rows(1, 8, 64, 512) != lm_data.rows(2, 8, 64, 512)).any()
    step = np.diff(a.astype(np.int64), axis=1) % 512
    assert ((step == step[:, :1]).all(axis=1)).all()  # learnable: a constant stride
    with pytest.raises(ValueError):
        lm_data.rows(0, 3 * 512 + 1, 8, 512)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 5.5), (90, 9.1), (99, 9.91), (100, 10.0)])
def test_percentile_is_numpys(q, want):
    xs = list(range(1, 11))
    assert stats.percentile(xs, q) == pytest.approx(want)
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_percentile_of_nothing_is_an_error_and_of_one_is_it():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    assert stats.percentile([3.0], 99) == 3.0


def test_latencies_are_taken_from_the_due_time_and_a_failed_request_misses_every_limit():
    reqs = [(10.0, 10.002, [10.5, 10.6, 10.9]),   # due, submitted, token times
            (11.0, 11.3, [12.0]),                  # sent 0.3 s late: the wait counts
            (12.0, 12.0, []),                      # no token by the deadline
            (13.0, None, [])]                      # refused at submit
    ttft, itl, late = stats.request_latencies(reqs, deadline=60.0)
    assert ttft == pytest.approx([0.5, 1.0, 48.0, 47.0])
    assert itl == pytest.approx([0.1, 0.3])
    assert late == pytest.approx([0.002, 0.3, 0.0])
    assert stats.percentile(ttft, 90) > 47.0  # two failures in four own the tail


def test_an_infinite_tail_stays_infinite():
    assert math.isinf(stats.percentile([0.1] * 8 + [math.inf, math.inf], 95))
    assert stats.percentile([0.1] * 8 + [math.inf, math.inf], 50) == pytest.approx(0.1)


def test_gaps_between_tokens():
    assert stats.gaps([1.0, 1.5, 3.0]) == [0.5, 1.5]
    assert stats.gaps([1.0]) == []
