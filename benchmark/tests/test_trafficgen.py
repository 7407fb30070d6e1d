"""The stratified generator: every seed offers the same multiset of work."""

import json
import os

import pytest

from benchmark import trafficgen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (0, 7, 3_000_000_007)


def _traffic(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_strata_are_the_midpoint_quantiles():
    assert trafficgen.strata({"dist": "uniform", "lo": 0, "hi": 100}, 4) == [12, 38, 62, 88]
    gaps = trafficgen.strata({"dist": "exponential", "mean": 2.0}, 1000, integer=False)
    assert abs(sum(gaps) / 1000 - 2.0) < 0.01        # the mean survives stratifying


def test_closed_loop_blocks_same_multiset_other_order():
    t = _traffic("decode-batch")
    plans = [trafficgen.closed_loop_plan(t, s) for s in SEEDS]
    blocks = [[p["block"](b) for b in range(3)] for p in plans]
    key = lambda reqs, k: sorted(r[k] for r in reqs)
    for k in ("prompt_tokens", "max_new_tokens"):
        want = key(blocks[0][0], k)
        for per_seed in blocks:
            for blk in per_seed:
                assert key(blk, k) == want              # same multiset, every block, every seed
    orders = {tuple(r["max_new_tokens"] for r in per_seed[0]) for per_seed in blocks}
    assert len(orders) == len(SEEDS)                    # but another order
    assert blocks[0][0] != blocks[0][1]                 # and block to block
    totals = {sum(r["prompt_tokens"] + r["max_new_tokens"] for blk in per_seed for r in blk)
              for per_seed in blocks}
    assert len(totals) == 1                             # total offered tokens equal across seeds
    # every sub-block of 9 spans the range: its offered tokens stay within 3% of a fifth
    for per_seed in blocks:
        for blk in per_seed:
            whole = sum(r["prompt_tokens"] + r["max_new_tokens"] for r in blk)
            for i in range(0, 45, t["sub_block_requests"]):
                part = sum(r["prompt_tokens"] + r["max_new_tokens"] for r in blk[i:i + 9])
                assert abs(part - whole / 5) < 0.03 * whole / 5
    lens = key(blocks[0][0], "max_new_tokens")
    assert lens[0] >= 128 and lens[-1] <= 512 and len(lens) == 45


def test_first_generation_phases_uniform_and_work_equal():
    t = _traffic("decode-batch")
    slots = t["engine"]["slots"]
    firsts = [trafficgen.closed_loop_plan(t, s)["first"] for s in SEEDS]
    for first in firsts:
        assert len(first) == slots
        phases = sorted(r["phase"] for r in first)
        assert phases == pytest.approx([(i + 0.5) / slots for i in range(slots)])
        for r in first:                                  # never more than the context holds
            assert r["prompt_tokens"] + r["max_new_tokens"] + t["engine"]["chunk"] <= 1024
            assert r["max_new_tokens"] >= 1
    own = lambda first, k: sorted(r[k] for r in first)
    assert own(firsts[0], "own_output") == own(firsts[1], "own_output") == own(firsts[2], "own_output")
    assert own(firsts[0], "own_prompt") == own(firsts[1], "own_prompt")
    # longer outputs are over-represented, as a closed loop holds them
    mean_first = sum(own(firsts[0], "own_output")) / slots
    assert mean_first > (128 + 512) / 2


def test_sessions_same_gaps_and_lengths_every_seed():
    t = _traffic("prefix-chat")
    rate, horizon = 1.0, 48.1
    plans = [trafficgen.session_plan(t, s, rate, horizon) for s in SEEDS]
    nb = t["block_sessions"]
    assert {len(p) for p in plans} == {int(horizon * rate)}   # blocks span nb/rate exactly
    for p in plans:
        starts = [s["start_s"] for s in p]
        assert starts == sorted(starts)
        assert p[nb - 1]["start_s"] == pytest.approx(nb / rate)
    def multiset(p, k):
        return sorted(x for s in p[:nb] for x in s[k])
    for k in ("user_tokens", "answer_tokens"):
        assert multiset(plans[0], k) == multiset(plans[1], k) == multiset(plans[2], k)
    gaps = lambda p: sorted(round(b["start_s"] - a["start_s"], 9)
                            for a, b in zip([{"start_s": 0.0}] + p[:nb - 1], p[:nb]))
    assert gaps(plans[0]) == gaps(plans[1])
    assert [s["start_s"] for s in plans[0]] != [s["start_s"] for s in plans[1]]
    # a third turn's prompt stays inside the context
    worst = t["system_prompt_tokens"] + 3 * 96 + 3 * 128
    assert worst + t["engine"]["chunk"] <= 1024


def test_token_ids_follow_the_seed_and_take_large_seeds():
    a = trafficgen.token_ids(trafficgen.rng_for(2 ** 31 + 5, "tok0"), 16, 50257)
    b = trafficgen.token_ids(trafficgen.rng_for(2 ** 31 + 5, "tok0"), 16, 50257)
    c = trafficgen.token_ids(trafficgen.rng_for(2 ** 31 + 6, "tok0"), 16, 50257)
    assert a == b and a != c and all(1 <= t < 50257 for t in a)
