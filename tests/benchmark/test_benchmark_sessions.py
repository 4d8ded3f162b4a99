"""The session generator and the percentile and rate arithmetic."""

import numpy as np
import pytest

from benchmark import stats
from benchmark.sessions import SessionPlan, length_pool, seeded_order

LENGTHS = {"pool": 32,
           "prompt": {"mean": 19.31, "sigma": 0.7, "min": 4, "max": 128},
           "output": {"mean": 58.45, "sigma": 0.6, "min": 8, "max": 256}}


def sessions(seed, count=40):
    plan = SessionPlan({"lengths": LENGTHS}, 50257, seed)
    return [plan.session(i) for i in range(count)]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_seed_same_sessions(seed):
    for a, b in zip(sessions(seed), sessions(seed)):
        assert a["tokens_out"] == b["tokens_out"]
        assert np.array_equal(a["prompt"], b["prompt"])


@pytest.mark.parametrize("seed, other", [(0, 1), (7, 2**31 + 7)])
def test_another_seed_the_same_sizes_in_another_order(seed, other):
    a, b = sessions(seed, 64), sessions(other, 64)
    for cycle in (slice(0, 32), slice(32, 64)):
        assert sorted(len(x["prompt"]) for x in a[cycle]) == sorted(
            len(y["prompt"]) for y in b[cycle])
        assert sorted(x["tokens_out"] for x in a[cycle]) == sorted(
            y["tokens_out"] for y in b[cycle])
    assert [len(x["prompt"]) for x in a] != [len(y["prompt"]) for y in b]
    assert [x["tokens_out"] for x in a] != [y["tokens_out"] for y in b]
    assert not any(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", [3, 2**31 + 3])
def test_one_cycle_is_the_whole_pool_and_the_next_is_drawn_anew(seed):
    two = sessions(seed, count=64)
    for cycle in (two[:32], two[32:]):
        assert sorted(len(s["prompt"]) for s in cycle) == sorted(
            length_pool(LENGTHS["prompt"], 32).tolist())
        assert sorted(s["tokens_out"] for s in cycle) == sorted(
            length_pool(LENGTHS["output"], 32).tolist())
    assert [s["tokens_out"] for s in two[:32]] != [s["tokens_out"] for s in two[32:]]


@pytest.mark.parametrize("count", [1, 2, 8, 12, 32])
def test_seeded_order_is_a_permutation(count):
    order = seeded_order(count, np.random.default_rng(count))
    assert sorted(order) == list(range(count))


@pytest.mark.parametrize("seed", [0, 5, 13, 2**31 + 24])
def test_every_block_of_eight_holds_one_of_each_octile(seed):
    order = seeded_order(32, np.random.default_rng(seed))
    for block in range(4):
        assert sorted(i // 4 for i in order[8 * block:8 * block + 8]) == list(range(8))


@pytest.mark.parametrize("seed", [1, 2**31 + 2])
def test_every_eight_sessions_in_a_row_carry_about_the_same_work(seed):
    blocks = [sessions(seed, 64)[at:at + 8] for at in range(0, 64, 8)]
    prompts = [sum(len(s["prompt"]) for s in b) for b in blocks]
    outputs = [sum(s["tokens_out"] for s in b) for b in blocks]
    assert max(prompts) < 1.6 * min(prompts) and max(outputs) < 1.5 * min(outputs)


def test_the_pairing_of_prompt_and_output_is_the_seeds():
    pairs = lambda seed: {(len(s["prompt"]), s["tokens_out"]) for s in sessions(seed, 32)}
    assert pairs(0) != pairs(1)


@pytest.mark.parametrize("spec, published", [(LENGTHS["prompt"], 19.31),
                                             (LENGTHS["output"], 58.45)])
def test_length_pool_is_the_clipped_lognormal_of_the_published_mean(spec, published):
    pool = length_pool(spec, 32)
    assert pool.min() >= spec["min"] and pool.max() <= spec["max"]
    assert abs(float(pool.mean()) - published) / published < 0.02
    assert np.all(np.diff(pool) >= 0)


def test_token_ids_cover_the_vocabulary_and_stay_inside_it():
    ids = np.concatenate([s["prompt"] for s in sessions(5, 200)])
    assert ids.min() >= 0 and ids.max() < 50257 and ids.max() > 40000


@pytest.mark.parametrize("values, q, want", [
    ([1.0], 95, 1.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([5.0, 1.0, 3.0], 0, 1.0),
    ([5.0, 1.0, 3.0], 100, 5.0),
])
def test_percentile_is_numpys(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(np.percentile(values, q))


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 50) is None


def steady(t_send, first, gap, n):
    return {"t_send": t_send, "token_times": [first + gap * i for i in range(n)]}


def test_window_counts_only_what_lies_inside_it():
    records = [steady(9.0, 9.5, 0.1, 20),      # begun before the window
               steady(10.0, 10.4, 0.1, 10),    # wholly inside
               steady(19.5, 19.9, 0.1, 10)]    # runs past the close
    m = stats.window_metrics(records, 10.0, 10.0)
    # tokens at 10.0 .. 11.4 of the first (15), 10 of the second, 19.9 and 20.0
    assert m["output_tokens_per_s"] == pytest.approx((15 + 10 + 2) / 10.0)
    assert m["sessions_timed"] == 2            # the first was begun at 9.0
    assert m["ttft_p50_ms"] == pytest.approx(400.0)
    assert m["gaps_timed"] == 15 + 9 + 1
    assert m["token_gap_p50_ms"] == pytest.approx(100.0)
    assert m["ttft_p95_ms"] == pytest.approx(400.0)


def test_a_stall_shows_in_the_tail_and_in_the_rate():
    smooth = [steady(0.0, 0.5, 0.05, 100)]
    times = smooth[0]["token_times"]
    stalled = [{"t_send": 0.0,
                "token_times": times[:50] + [t + 3.0 for t in times[50:]]}]
    a = stats.window_metrics(smooth, 0.0, 6.0)
    b = stats.window_metrics(stalled, 0.0, 6.0)
    assert a["token_gap_p95_ms"] == pytest.approx(50.0)
    # one gap of 3.05 s among 99: the 95th percentile does not see it, the
    # rate does, and the window is as long as it was
    assert b["output_tokens_per_s"] < a["output_tokens_per_s"]
    assert b["output_tokens_per_s"] == pytest.approx(
        sum(1 for t in stalled[0]["token_times"] if t <= 6.0) / 6.0)
    assert b["token_gap_p95_ms"] == pytest.approx(50.0)
    gaps = [(y - x) * 1e3 for x, y in zip(stalled[0]["token_times"],
                                          stalled[0]["token_times"][1:])]
    assert stats.percentile(gaps, 100) == pytest.approx(3050.0)


def test_no_sessions_no_tails():
    m = stats.window_metrics([], 0.0, 5.0)
    assert m["output_tokens_per_s"] == 0.0 and m["ttft_p95_ms"] is None


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_peaks_of_the_v5e(kind):
    peaks = stats.peaks_for(kind)
    assert peaks["flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        stats.peaks_for("TPU v9")
