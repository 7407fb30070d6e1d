"""The trace reduction: on a trace small enough to work by hand, and on a
small trace recorded on the chip (``data/small_trace.json``)."""

import json
import os

import pytest

from benchmark.readers import client, device
from benchmark.reduce import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1_000


def _labels():
    with open(os.path.join(HERE, "..", "reduce", "host_spans.json")) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


# A device that runs, in microseconds:
#   a [0,100)  b [50,150) (overlaps a)  -> busy [0,150)
#   gap [150,400): 250 us; the host dispatches a prefill over [160,390)
#   k [400,500)  k [500,560)            -> busy [400,560)
#   gap [560,590): 30 us, under 50 us
#   c [590,600)                          -> busy [590,600)
# window 0..600 us; busy 150 + 160 + 10 = 320 us; idle share 280/600.
HAND = {
    "devices": {"/device:TPU:0": {
        "XLA Ops": [["a:fusion:f32[8]", 0, 100 * US], ["b:fusion:f32[8]", 50 * US, 100 * US],
                    ["w:while:s32[]", 400 * US, 160 * US],
                    ["k:custom-call:bf16[4,2,1,8]", 400 * US, 100 * US],
                    ["k:custom-call:bf16[4,2,1,8]", 500 * US, 60 * US],
                    ["c:fusion:f32[8]", 590 * US, 10 * US]],
        "XLA Modules": [["jit_paged_decode(1)", 0, 150 * US],
                        ["jit_paged_prefill(2)", 400 * US, 200 * US]]}},
    "host": [["t", "PjitFunction(paged_prefill)", 160 * US, 230 * US],
             ["t", "PjitFunction(paged_decode)", 380 * US, 15 * US]],
}


def test_busy_idle_and_kernel_time_by_hand():
    b = tr.busy(HAND)
    assert b["window_s"] == pytest.approx(600e-6)
    assert b["busy_s"] == pytest.approx(320e-6)
    k = tr.op_seconds(HAND, r":custom-call:")
    assert k == {"seconds": pytest.approx(160e-6), "count": 2.0}
    # the while holds its children's time and is in no sum
    assert tr.op_seconds(HAND, r"^w:")["count"] == 0
    assert [n for n, _ in tr.top_ops(HAND, 2)] == ["k:custom-call:bf16[4,2,1,8]", "a:fusion:f32[8]"]
    m = tr.op_seconds(HAND, "^jit_paged_prefill", tr.MODULES_LINE)
    assert m["seconds"] == pytest.approx(200e-6) and m["count"] == 1


def test_gap_attribution_by_hand():
    gaps = dict(tr.idle_gaps(HAND, _labels()))
    # 250 us gap: the prefill dispatch covers 230 us of it, the decode dispatch 15
    assert gaps == {"engine_dispatches_paged_prefill": pytest.approx(250e-6),
                    "gaps-under-50us": pytest.approx(30e-6)}
    no_host = dict(HAND, host=[])
    assert dict(tr.idle_gaps(no_host, _labels()))["host-unattributed"] == pytest.approx(250e-6)


def test_collectives_exposed_by_hand():
    # all-gather [0,100); compute [60,200): 60 us of the collective are exposed
    # and a while that spans everything (a container: it hides nothing);
    # an asynchronous reduce-scatter [150,260) on its own line: 60 us exposed
    t = {"devices": {"/device:TPU:0": {"XLA Ops": [
        ["while:while:s32[]", 0, 300 * US],
        ["all-gather:all-gather:bf16[8]", 0, 100 * US],
        ["fusion:fusion:f32[8]", 60 * US, 140 * US]],
        "Async XLA Ops": [["reduce-scatter-start:reduce-scatter-start:f32[8]", 150 * US, 110 * US]]}},
        "host": []}
    e = tr.collective_exposed_seconds(t)
    assert e["total_s"] == pytest.approx(210e-6) and e["exposed_s"] == pytest.approx(120e-6)


# A 1000 us trace of a train step that takes 300 us, as the profiler records
# it: the session starts 200 us before a step ends and stops 200 us into
# another, and both are clipped to its edges. Five module events that fill
# the trace are two whole steps and two parts. Each step runs one flash
# kernel of 100 us, 150 us after it starts: the first part holds none (its
# kernel ran before the session), the last part holds 50 us of one.
#   modules [0,200) [200,500) [500,800) [800,1000)   <- first and last clipped
#   kernels         [350,450) [650,750) [950,1000)
CLIPPED = {
    "devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_step(7)", 0, 200 * US], ["jit_step(7)", 200 * US, 300 * US],
                        ["jit_step(7)", 500 * US, 300 * US], ["jit_step(7)", 800 * US, 200 * US]],
        "XLA Ops": [["mm:fusion:bf16[8,8]", 0, 200 * US],
                    ["mm:fusion:bf16[8,8]", 200 * US, 150 * US],
                    ["flash:custom-call:bf16[2,8,4]", 350 * US, 100 * US],
                    ["mm:fusion:bf16[8,8]", 450 * US, 200 * US],
                    ["flash:custom-call:bf16[2,8,4]", 650 * US, 100 * US],
                    ["mm:fusion:bf16[8,8]", 750 * US, 200 * US],
                    ["flash:custom-call:bf16[2,8,4]", 950 * US, 50 * US]]}},
    "host": [],
}


def test_events_clipped_at_the_trace_edges_are_not_counted():
    whole = tr.whole_events(CLIPPED, "^jit_step")
    assert whole == {"/device:TPU:0": [(200 * US, 500 * US), (500 * US, 800 * US)]}
    assert tr.whole_seconds(CLIPPED, "^jit_step") == {"seconds": pytest.approx(600e-6), "count": 2.0}
    # every event of the line counts four "steps" in 1000 us; there are 3 1/3
    assert tr.op_seconds(CLIPPED, "^jit_step", tr.MODULES_LINE)["count"] == 4
    k = tr.op_seconds(CLIPPED, ":custom-call:", inside=whole)
    assert k == {"seconds": pytest.approx(200e-6), "count": 2.0}   # not the clipped 50 us
    # the recorded trace holds one whole program (the prefill) between two parts
    t = tr.load(os.path.join(HERE, "data", "small_trace.json"))
    assert tr.whole_seconds(t, "^jit_paged_decode")["count"] == 0
    assert tr.whole_seconds(t, "^jit_paged_prefill") == {"seconds": pytest.approx(48_328_046e-9), "count": 1.0}


def test_flash_roofline_and_step_time_over_whole_steps_by_hand():
    # one sequence of 8 tokens, 2 heads of 4, one layer: forward 2*T*T*D = 512
    # FLOPs a head, backward twice that -> 2 * 3 * 512 = 3072 a step; two whole
    # steps = 6144 FLOPs in 200 us of kernel = 30.72 MFLOP/s; peak 61.44 -> 50%.
    # Counting all four module events against all 250 us of kernel would give
    # 12288 / 250 us = 49.152 -> 80%: the fault the clipped edges made.
    run = {"trace": CLIPPED, "config": {"n_head": 2, "head_dim": 4, "n_layer": 1},
           "global_batch": 1, "chips": 1, "sequence_tokens": 8,
           "peaks": {"bf16_flops_per_s": 61.44e6}, "chunk": 3}
    spec = {"pattern": ":custom-call:bf16", "step_pattern": "^jit_step"}
    assert device.flash_attn_roofline(run, spec) == pytest.approx(50.0)
    # as a decode program of 3 steps to a call: 600 us / (2 calls * 3) = 100 us
    assert device.program_step_ms(run, {"pattern": "^jit_step"}) == pytest.approx(0.1)
    assert device.flash_attn_roofline(dict(run, trace=None), spec) is None
    # a trace too short to hold a whole step gives nothing, not a guess
    short = {"devices": {"/device:TPU:0": {k: v[:2] for k, v in
                                           CLIPPED["devices"]["/device:TPU:0"].items()}}, "host": []}
    assert device.flash_attn_roofline(dict(run, trace=short), spec) is None
    assert device.program_step_ms(dict(run, trace=short), {"pattern": "^jit_step"}) is None


def test_paged_roofline_takes_bytes_and_kernel_time_from_the_same_interval():
    # HAND's device window is [0,600) us on the trace's clock, which starts at
    # host time 10.0 s. Two tokens arrive inside it (contexts 5+1 and 7+0), one
    # after it (not counted, though the run's window holds it). One layer, 2
    # heads of 8, bf16: 2*8*2*2 = 64 bytes a context token -> 13 * 64 = 832
    # bytes. Kernel 160 us. At a peak of 10.4 MB/s the least time is 80 us: 50%.
    run = {"trace": HAND, "trace_host_t0": 10.0, "t_open": 9.0, "t_close": 12.0,
           "config": {"n_head": 2, "head_dim": 8, "n_layer": 1},
           "peaks": {"hbm_bytes_per_s": 10.4e6},
           "records": [{"prompt_tokens": 5, "times": [9.9999, 10.0001]},
                       {"prompt_tokens": 7, "times": [10.0005, 10.0007]}]}
    spec = {"pattern": r":custom-call:bf16\[\d+,\d+,1,\d+\]"}
    assert client.kv_read_bytes(run, 2, 8, 1, 10.0, 10.0006) == 832
    assert device.paged_attn_roofline(run, spec) == pytest.approx(50.0)


def test_latency_per_token_by_hand():
    # due at 1.0, three tokens, the last at 1.6 -> 200 ms a token; due at 2.0,
    # one token at 2.1 -> 100 ms; a request due outside the window is left out
    recs = [{"in_window": True, "due_t": 1.0, "times": [1.2, 1.4, 1.6], "tokens": [1, 2, 3],
             "asked": 3, "finish_reason": "length"},
            {"in_window": True, "due_t": 2.0, "times": [2.1], "tokens": [1], "asked": 1,
             "finish_reason": "length"},
            {"in_window": False, "due_t": 0.5, "times": [0.9], "tokens": [1], "asked": 1,
             "finish_reason": "length"}]
    run = {"records": recs, "t_open": 1.0, "t_close": 3.0}
    assert client.latency_per_token_ms(run, {}) == pytest.approx(150.0)


def test_short_name():
    text = ("%closed_call.269 = bf16[36,16,1,64]{3,2,1,0:T(2,128)(2,1)S(1)} custom-call("
            "s32[36,64]{1,0:T(8,128)S(1)} %get-tuple-element.1), custom_call_target=\"tpu_custom_call\"")
    assert tr.short_name(text) == "closed_call:custom-call:bf16[36,16,1,64]"
    assert tr.short_name("%while.6 = (s32[]{:T(128)}, bf16[24,1281,16,16,64]{4,3}) while((s32[]) %t)") \
        == "while:while:s32[]"
    assert tr.short_name("%fusion.12.clone.3 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion:fusion:f32[8]"
    assert tr.short_name("jit_step(123)") == "jit_step(123)"


def _sweep_busy(events):
    """An independent reckoning of the union's length: a sweep over sorted
    starts that carries the furthest end seen."""
    total, end = 0, None
    for s, e in sorted((s, s + d) for _n, s, d in events):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def test_recorded_trace():
    t = tr.load(os.path.join(HERE, "data", "small_trace.json"))
    ops = t["devices"]["/device:TPU:0"]["XLA Ops"]
    mods = t["devices"]["/device:TPU:0"]["XLA Modules"]
    b = tr.busy(t)
    first = min(s for _n, s, _d in ops)
    last = max(s + d for _n, s, d in ops)
    assert b["window_s"] == pytest.approx((last - first) / 1e9)
    assert b["busy_s"] == pytest.approx(_sweep_busy(ops) / 1e9, rel=1e-9)
    # Read off the module line by hand: the decode program ends at
    # 240,000,000 + 8,018,993 = 248,018,993 ns and the prefill program starts
    # at 351,415,348 ns: 103,396,355 ns with nothing on the device, while the
    # host was inside PjitFunction(paged_prefill) (263.5 .. 353.0 ms).
    assert mods[0][1] + mods[0][2] == 248_018_993 and mods[1][1] == 351_415_348
    gaps = dict(tr.idle_gaps(t, _labels()))
    assert gaps["engine_dispatches_paged_prefill"] == pytest.approx(103_396_355e-9, abs=5e-6)
    assert 1.0 - b["busy_s"] / b["window_s"] == pytest.approx(0.64, abs=0.01)
    # the prefill program: one run of 48,328,046 ns; its kernel 24 calls (one a layer)
    m = tr.op_seconds(t, "^jit_paged_prefill", tr.MODULES_LINE)
    assert m == {"seconds": pytest.approx(48_328_046e-9), "count": 1.0}
    k = tr.op_seconds(t, r":custom-call:bf16\[\d+,\d+,\d+,\d+\]")
    assert k["count"] == 24
    by_hand = sum(d for n, _s, d in ops if n.startswith("paged_prefill:custom-call"))
    assert k["seconds"] == pytest.approx(by_hand / 1e9) and 0.007 < k["seconds"] < 0.008
    # the whole KV pool is copied inside the prefill program: the top operation
    assert tr.top_ops(t, 1)[0][0] == "copy:copy:bf16[24,1281,16,16,64]"
