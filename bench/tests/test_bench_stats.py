"""The arithmetic of the end-to-end metrics and the traffic generators,
on the CPU: percentiles over every sample, time to first token censored at
the window's end, inter-token gaps, the open loop's lateness, and traffic
that depends on ``--seed`` only through its tokens."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import spec, stats  # noqa: E402
from bench.traffic import closed_loop, open_loop  # noqa: E402

YI = {"name": "yi-6b", "vocab_size": 64000}
RATE = {"rate_per_s": 2.0}


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_over_all_samples(q):
    xs = np.random.default_rng(3).lognormal(size=257)
    assert stats.percentile(list(xs), q) == pytest.approx(
        float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_edges():
    assert stats.percentile([], 90) is None
    assert stats.percentile([4.0], 99) == 4.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5


def test_ttft_counts_from_due_and_censors_at_window_end():
    reqs = [
        {"due": 1.0, "submitted": 1.2, "first_token": 1.5},
        {"due": 2.0, "submitted": 2.0, "first_token": None},   # never
        {"due": 3.0, "submitted": 3.1, "first_token": 9.0},    # after end
    ]
    assert stats.ttft_samples(reqs, t_end=5.0) == pytest.approx(
        [0.5, 3.0, 2.0])
    assert stats.lateness(reqs) == pytest.approx([0.2, 0.0, 0.1])


def test_inter_token_gaps_keep_only_gaps_ending_in_the_window():
    times = {1: [0.5, 1.5, 2.0, 4.5], 2: [3.0]}
    # 1.5-0.5 ends before the window; 4.5-2.0 is a parked request's gap
    assert stats.inter_token_gaps(times, 1.6, 5.0) == pytest.approx(
        [0.5, 2.5])


def test_mean_is_every_sample_over_their_count():
    times = {1: [0.5, 1.5, 2.0, 4.5], 2: [3.0], 3: [2.0, 2.5]}
    gaps = stats.inter_token_gaps(times, 1.6, 5.0)    # 0.5, 2.5, 0.5
    assert stats.mean(gaps) == pytest.approx(3.5 / 3)
    assert stats.mean([]) is None


def test_time_per_output_token_is_each_requests_mean_gap_in_window():
    times = {1: [0.5, 1.5, 2.0, 4.5], 2: [3.0], 3: [2.0, 2.5]}
    # request 1: tokens 2.0 and 4.5 inside (1.6, 5.0]; 2: one token
    assert stats.time_per_output_token(times, 1.6, 5.0) == pytest.approx(
        [2.5, 0.5])


def test_open_loop_schedule_is_fixed_and_seed_draws_tokens():
    mix = spec.traffic_file("chat_shared")
    a = open_loop.make(mix, 11, YI, RATE)
    b = open_loop.make(mix, 11, YI, RATE)
    c = open_loop.make(mix, 12, YI, RATE)
    ra, rb, rc = a.due(30.0), b.due(30.0), c.due(30.0)
    assert len(ra) == len(rc) > 20
    for x, y, z in zip(ra, rb, rc):
        assert x["due"] == z["due"] and x["max_new"] == z["max_new"]
        assert x["tenant"] == z["tenant"]
        assert len(x["prompt"]) == len(z["prompt"])
        assert np.array_equal(x["prompt"], y["prompt"])
    assert any(not np.array_equal(x["prompt"], z["prompt"])
               for x, z in zip(ra, rc))
    # a tenant's system prompt is shared by all its requests
    sys_len = {t["name"]: t["system_tokens"] for t in mix["tenants"]}
    gold = [r for r in ra if r["tenant"] == "gold"]
    assert len(gold) >= 2
    n = sys_len["gold"]
    assert all(np.array_equal(gold[0]["prompt"][:n], g["prompt"][:n])
               for g in gold)
    assert {len(r["prompt"]) for r in ra} <= set(a.prompt_lengths)


def test_open_loop_rate_and_burstiness():
    mix = spec.traffic_file("chat_shared")
    src = open_loop.make(mix, 1, YI, {"rate_per_s": 2.0})
    due = np.asarray(src._due)
    gaps = np.diff(due)
    assert len(due) / due[-1] == pytest.approx(2.0, rel=0.1)
    assert gaps.std() / gaps.mean() == pytest.approx(mix["cv"], rel=0.15)
    assert src.due(due[4]) and src.next_due() == pytest.approx(due[5])


def test_closed_loop_clients_resubmit_on_finish():
    mix = spec.traffic_file("decode_long")
    src = closed_loop.make(mix, 5, YI)
    first = src.due(0.0)
    assert len(first) == mix["clients"]
    assert src.due(1.0) == [] and src.next_due() is None
    # first requests are cut short in proportion to the client's index
    outs = [r["max_new"] for r in first]
    assert outs[0] < outs[-1]
    src.finished(first[3]["key"], 2.5)
    nxt = src.due(2.5)
    assert len(nxt) == 1 and nxt[0]["due"] == 2.5
    lo, hi = mix["output_tokens"]["min"], mix["output_tokens"]["max"]
    assert lo <= nxt[0]["max_new"] <= hi
    assert len(nxt[0]["prompt"]) in mix["prompt_tokens"]["values"]
