"""Sessions in an open loop: conversations start on a schedule fixed by the
traffic file, whatever the server does; a turn's prompt is the system prompt
plus the whole history with the answers the server really gave, and the next
turn is due ``think_s`` after the last token of the one before."""

from __future__ import annotations

import heapq
import threading
import time
from typing import Dict, List

from benchmark import trafficgen
from benchmark.drivers import common
from benchmark.drivers.serve import Served, StatsPoller, check_sample, ok_request


def run(ctx: Dict) -> Dict:
    served = Served(ctx)
    try:
        if ctx.get("sweep"):
            return {"sweep": [
                _summary(_run(ctx, served, float(rate), ctx["seed"] + i), rate)
                for i, rate in enumerate(ctx["sweep"])]}
        return _run(ctx, served, float(ctx["traffic"]["sessions_per_s"]),
                    ctx["seed"])
    finally:
        served.close()


def _run(ctx, served: Served, rate: float, seed: int) -> Dict:
    traffic, seconds = ctx["traffic"], float(ctx["seconds"])
    vocab = served.cfg.vocab_size
    warm = float(traffic["warm_s"])
    think = float(traffic["think_s"])
    turns = int(traffic["turns"])
    plan = trafficgen.session_plan(traffic, seed, rate, warm + seconds)
    sys_prompts = [trafficgen.token_ids(
        trafficgen.rng_for(seed, f"sys{i}"),
        int(traffic["system_prompt_tokens"]), vocab)
        for i in range(int(traffic["system_prompts"]))]

    recs: List[Dict] = []
    prompts: List[List[int]] = []
    lock = threading.Lock()
    cond = threading.Condition(lock)
    heap: List = []           # (due_t, tiebreak, session, turn, history)
    inflight = {"n": 0}
    t0 = time.perf_counter() + 0.2
    t_open, t_close = t0 + warm, t0 + warm + seconds
    for s in plan:
        heapq.heappush(heap, (t0 + s["start_s"], s["id"], s, 0, None))

    def serve_turn(due: float, s: Dict, turn: int, history: List[int]) -> None:
        urng = trafficgen.rng_for(seed, f"usr{s['id']}.{turn}")
        if history is None:
            history = list(sys_prompts[s["system_prompt"]])
        prompt = history + trafficgen.token_ids(
            urng, s["user_tokens"][turn], vocab)
        with lock:
            rec = {"idx": len(recs), "session": s["id"], "turn": turn,
                   "due_t": due, "in_window": t_open <= due < t_close}
            recs.append(rec)
            prompts.append(prompt)
        served.stream(prompt, s["answer_tokens"][turn], rec)
        with cond:
            inflight["n"] -= 1
            nxt = rec["done_t"] + think
            if turn + 1 < turns and ok_request(rec) and nxt < t_close:
                heapq.heappush(heap, (nxt, s["id"], s, turn + 1,
                                      prompt + rec["tokens"]))
            cond.notify_all()

    def dispatcher() -> None:
        while True:
            with cond:
                while True:
                    now = time.perf_counter()
                    if heap and heap[0][0] <= now:
                        due, _, s, turn, hist = heapq.heappop(heap)
                        inflight["n"] += 1
                        break
                    if not heap and (inflight["n"] == 0 or now >= t_close):
                        return
                    wait = (heap[0][0] - now) if heap else 0.05
                    cond.wait(timeout=min(max(wait, 0.0), 0.05))
            threading.Thread(target=serve_turn, args=(due, s, turn, hist),
                             daemon=True, name="bench-turn").start()

    disp = threading.Thread(target=dispatcher, name="bench-dispatch",
                            daemon=True)
    disp.start()
    time.sleep(max(0.0, t_open - time.perf_counter()))
    poller = StatsPoller(served)
    poller.start()
    before = served.engine_stats()
    q_before = served.queued_phase()
    tracer = common.trace_window(ctx, t_open, seconds)
    time.sleep(max(0.0, t_close - time.perf_counter()))
    after = served.engine_stats()
    q_after = served.queued_phase()
    poller.stop()
    if tracer is not None:
        tracer.join()
    # Drain what became due inside the window, within the stated limit.
    limit = time.perf_counter() + float(traffic["drain_limit_s"])
    with cond:
        while inflight["n"] > 0 and time.perf_counter() < limit:
            cond.wait(timeout=0.1)
        undrained = inflight["n"]
    disp.join(timeout=1.0)

    with lock:
        all_recs = [dict(r) for r in recs]
    in_win = [r for r in all_recs if r["in_window"]]
    failed = [r for r in in_win if not ok_request(r)]
    check = check_sample(
        served, all_recs, prompts, int(traffic["check"]["requests"]),
        float(traffic["check"]["logit_tolerance"]),
        trafficgen.rng_for(seed, "check"))
    return {
        "t_open": t_open, "t_close": t_close, "window_s": t_close - t_open,
        "attempted": len(in_win), "failed": len(failed),
        "correct_parts": {"streams_complete": not failed and not undrained,
                          "reference_sample": check["ok"]},
        "check": check, "records": all_recs, "chunk": served.chunk,
        "counters": {"before": before, "after": after,
                     "queued_before": q_before, "queued_after": q_after,
                     "polled": poller.samples},
        "tracer": tracer,
        "realised": {"sessions": len(plan)}, "phases": served.phases,
    }


def _summary(run: Dict, rate: float) -> Dict:
    """One line of the knee sweep (the builder's tool, never a result)."""
    from benchmark.readers import client
    from benchmark.reduce.stats import percentile

    ttft = client.ttfts_ms(run)
    tpot = client.tpots_ms(run)
    polled = run["counters"]["polled"]
    depth = [s.get("queue_depth", 0.0) for s in polled]
    half = len(depth) // 2
    return {"rate": rate, "requests": run["attempted"], "failed": run["failed"],
            "ttft_p50": percentile(ttft, 50), "ttft_p90": percentile(ttft, 90),
            "tpot_p50": percentile(tpot, 50), "tpot_p90": percentile(tpot, 90),
            "ttfts": ttft, "tpots": tpot,
            "queue_depth_mean_first_half": sum(depth[:half]) / max(1, half),
            "queue_depth_mean_second_half": sum(depth[half:]) / max(1, len(depth) - half),
            "slots_busy_mean": sum(s.get("slots_busy", 0) for s in polled) / max(1, len(polled))}
