"""The traffic generator and the window arithmetic."""
import collections
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import traffic  # noqa: E402

SEEDS = (0, 7, 3_000_000_019)     # the driver's seeds pass 2**31


@pytest.mark.parametrize("name", ["chat-open", "longprompt-batch"])
def test_every_seed_gives_the_same_multiset_of_lengths(name):
    tf = traffic.load_traffic(name)
    n = len(tf["prompt_lens"])
    count = 3 * n
    span = dict(count=count, until_s=count / tf.get("rate_per_s", 1.0))
    plans = [traffic.plan_requests(tf, s, 1000, **span) for s in SEEDS]
    for plan in plans:
        assert len(plan) == count
        for b in range(3):                      # block by block
            block = plan[b * n:(b + 1) * n]
            assert sorted(len(r.prompt) for r in block) == sorted(
                tf["prompt_lens"])
            assert sorted(r.max_new for r in block) == sorted(
                tf["output_lens"])
    orders = {tuple(len(r.prompt) for r in p) for p in plans}
    assert len(orders) == len(SEEDS), "the seed must permute the order"
    pairs = [collections.Counter((len(r.prompt), r.max_new) for r in p)
             for p in plans]
    assert pairs[0] != pairs[1], "the seed must re-pair prompts and outputs"


def test_sub_blocks_hold_the_same_lengths_and_gaps_for_every_seed():
    tf = traffic.load_traffic("chat-open")
    n, strata = len(tf["prompt_lens"]), tf["strata"]
    sub, block_s = n // strata, n / tf["rate_per_s"]
    plans = [traffic.plan_requests(tf, s, 1000, until_s=block_s)
             for s in SEEDS]
    for j in range(strata):
        sets = {tuple(sorted(len(r.prompt) for r in p[j * sub:(j + 1) * sub]))
                for p in plans}
        assert len(sets) == 1
    assert all(len(p) == n and p[0].due == 0.0 for p in plans)
    gaps = {tuple(np.round(sorted(np.diff([r.due for r in p]
                                          + [block_s])), 9)) for p in plans}
    assert len(gaps) == 1, "every seed has the same gaps, in another order"


def test_the_window_of_the_chat_cell_holds_two_whole_blocks():
    """Block boundaries lie on the clock: at the file's rate the 50 s of
    BENCHMARK.json hold the same 128 requests for every seed, and the
    ramp is the tail of the block before."""
    tf = traffic.load_traffic("chat-open")
    n = len(tf["prompt_lens"])
    for s in SEEDS:
        plan = traffic.plan_requests(tf, s, 1000, ramp_s=tf["ramp_s"],
                                     until_s=60.0)
        assert plan[0].due >= -tf["ramp_s"] and plan[-1].due < 60.0
        assert [r.due for r in plan] == sorted(r.due for r in plan)
        inside = [r for r in plan if 0.0 <= r.due < 50.0]
        assert len(inside) == 2 * n and inside[0].due == 0.0
        assert sorted(len(r.prompt) for r in inside) == sorted(
            2 * tf["prompt_lens"])
        assert sorted(r.max_new for r in inside) == sorted(
            2 * tf["output_lens"])
        assert len({r.rid for r in plan}) == len(plan)


def test_arrivals_and_tokens_replay_for_a_seed():
    tf = traffic.load_traffic("chat-open")
    a = traffic.plan_requests(tf, 3_000_000_019, 92544, ramp_s=9.0,
                              until_s=30.0)
    b = traffic.plan_requests(tf, 3_000_000_019, 92544, ramp_s=9.0,
                              until_s=30.0)
    assert [r.due for r in a] == [r.due for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    dues = np.array([r.due for r in a])
    assert (np.diff(dues) > 0).all()
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 92544
               for r in a)


def test_closed_loop_requests_are_due_at_once():
    tf = traffic.load_traffic("longprompt-batch")
    plan = traffic.plan_requests(tf, 1, 1000, count=70)
    assert len(plan) == 70 and {r.due for r in plan} == {0.0}


def test_exponential_gap_quantiles_have_the_rate_as_mean():
    g = traffic.exponential_gap_quantiles(64, 2.5)
    assert g.mean() == pytest.approx(1 / 2.5)
    assert g.min() > 0 and g.max() / g.min() > 100   # heavy right tail


def test_train_batches_are_fresh_and_seeded():
    tf = {"global_batch": 2, "seq_len": 16}
    g1, g2 = (traffic.train_batches(tf, 5, 100) for _ in range(2))
    a, b = next(g1), next(g1)
    assert (a["input_ids"] != b["input_ids"]).any()
    assert (next(g2)["input_ids"] == a["input_ids"]).all()
    assert a["input_ids"].shape == (2, 16) and a["labels"] is a["input_ids"]


def test_jax_seed_fits_32_bits_for_large_seeds():
    for s in SEEDS:
        assert 0 <= traffic.jax_seed(s) < 2 ** 31 - 1
    assert traffic.jax_seed(SEEDS[1]) != traffic.jax_seed(SEEDS[2])


def test_one_stall_moves_the_rate_and_stall_pct_but_not_the_median():
    steady = np.arange(0, 101) * 0.324
    stalled = steady.copy()
    stalled[50:] += 0.4                         # one stalled step
    a = traffic.step_intervals(list(steady))
    b = traffic.step_intervals(list(stalled))
    assert a["rate_hz"] == pytest.approx(1 / 0.324, rel=1e-12)
    assert a["stall_pct"] == pytest.approx(0.0, abs=1e-9)
    # all the steps over all the time: the stall is in the rate (1.2%) ...
    assert b["rate_hz"] == pytest.approx(100 / 32.8, rel=1e-12)
    assert b["rate_hz"] / a["rate_hz"] < 0.99
    assert b["stall_pct"] == pytest.approx(100 * 0.4 / (32.4 + 0.4), rel=1e-6)
    # ... and not in the median, which is recorded beside it
    assert b["median_s"] == pytest.approx(a["median_s"], rel=1e-12)


def test_a_stall_of_every_fourth_step_is_in_the_rate():
    iv = np.tile([0.324, 0.324, 0.324, 0.424], 25)
    c = np.concatenate([[0.0], np.cumsum(iv)])
    r = traffic.step_intervals(list(c))
    assert r["median_s"] == pytest.approx(0.324)
    assert r["rate_hz"] == pytest.approx(100 / iv.sum())
    assert r["stall_pct"] == pytest.approx(100 * 2.5 / iv.sum())


def test_slice_rates_with_one_injected_stall():
    ends = list(np.arange(1, 161) * 0.0625)     # 160 steps of 62.5 ms
    toks = [170] * 160
    a = traffic.slice_rates(ends, toks, 0.0, 16)
    assert len(a["slice_rates"]) == 10
    assert a["rate"] == pytest.approx(170 / 0.0625)
    assert a["stall_pct"] == pytest.approx(0.0, abs=1e-9)
    stalled = [t + (1.0 if i >= 40 else 0.0) for i, t in enumerate(ends)]
    b = traffic.slice_rates(stalled, toks, 0.0, 16)
    # the rate is of all the work and all the time: the stall counts
    assert b["rate"] == pytest.approx(160 * 170 / 11.0)
    assert np.median(b["slice_rates"]) == pytest.approx(170 / 0.0625)
    assert min(b["slice_rates"]) < 0.6 * a["rate"]
    assert b["stall_pct"] == pytest.approx(100 * 1.0 / 11.0, rel=1e-6)
    # whole slices only for stall_pct: the 8 steps past the last full
    # slice are left out of it, but not out of the rate
    c = traffic.slice_rates(ends[:152], toks[:152], 0.0, 16)
    assert len(c["slice_rates"]) == 9
    assert c["rate"] == pytest.approx(170 / 0.0625)


def test_percentile_is_of_all_values():
    assert traffic.percentile(list(range(101)), 90) == pytest.approx(90.0)
    with pytest.raises(ValueError):
        traffic.percentile([], 90)
