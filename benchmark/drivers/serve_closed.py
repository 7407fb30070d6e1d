"""Closed loop: ``clients`` callers, each sending its next request when the
last one ended. The first generation is built so that the window opens on a
server already in steady state (see ``trafficgen.closed_loop_plan``)."""

from __future__ import annotations

import threading
import time
from typing import Dict, List

from benchmark import trafficgen
from benchmark.drivers import common
from benchmark.drivers.serve import Served, StatsPoller, check_sample, ok_request


def run(ctx: Dict) -> Dict:
    traffic, seconds = ctx["traffic"], float(ctx["seconds"])
    served = Served(ctx)
    try:
        return _run(ctx, served, traffic, seconds)
    finally:
        served.close()


def _run(ctx, served: Served, traffic: Dict, seconds: float) -> Dict:
    vocab = served.cfg.vocab_size
    plan = trafficgen.closed_loop_plan(traffic, ctx["seed"])
    slots, clients = int(traffic["engine"]["slots"]), int(traffic["clients"])
    chunk = served.chunk
    recs: List[Dict] = []
    prompts: List[List[int]] = []
    lock = threading.Lock()
    stop = threading.Event()
    state = {"block": 0, "queue": []}

    def next_request() -> Dict:
        with lock:
            if not state["queue"]:
                state["queue"] = plan["block"](state["block"])
                state["block"] += 1
            return state["queue"].pop(0)

    def issue(req: Dict, generation: int) -> None:
        with lock:
            idx = len(recs)
            rec = {"idx": idx, "generation": generation}
            recs.append(rec)
            prompts.append(trafficgen.token_ids(
                trafficgen.rng_for(ctx["seed"], f"tok{idx}"),
                req["prompt_tokens"], vocab))
        served.stream(prompts[idx], req["max_new_tokens"], rec)

    def client(first: Dict = None) -> None:
        if first is not None:
            issue(first, 0)
        while not stop.is_set():
            issue(next_request(), 1)

    threads = []
    # The first generation goes in one by one, in the planned order: the
    # engine admits first come, first served, one prompt to a step.
    for k in range(clients):
        first = plan["first"][k] if k < slots else None
        th = threading.Thread(target=client, args=(first,), daemon=True,
                              name=f"bench-client-{k}")
        th.start()
        threads.append(th)
        time.sleep(0.005)

    # The window opens once the whole first generation is sent and the engine
    # is full (every slot busy, or the pool cannot take one more request),
    # and `pre_window_chunks` decode chunks more have gone by.
    need = (1 + int(traffic["start"]["pre_window_chunks"])) * chunk
    deadline = time.perf_counter() + float(traffic["start"]["fill_limit_s"])
    while time.perf_counter() < deadline:
        with lock:
            gen0 = [r for r in recs if r["generation"] == 0]
        if (len(gen0) == slots and all("send_t" in r for r in gen0)
                and _filled(served, slots, int(
                    traffic["start"]["full_when_free_blocks_under"]))):
            break
        time.sleep(0.05)
    time.sleep(need * float(traffic["start"]["token_s_estimate"]))

    served.phases["slots_filled"] = time.perf_counter()
    poller = StatsPoller(served)
    poller.start()
    before = served.engine_stats()
    t_open = time.perf_counter()
    tracer = common.trace_window(ctx, t_open, seconds)
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    after = served.engine_stats()
    stop.set()
    poller.stop()
    if tracer is not None:
        tracer.join()
    # Streams still running finish on their own (the handle has no cancel);
    # they are sent, so they count as attempted, but their tokens after
    # t_close count for nothing.
    limit = time.perf_counter() + float(traffic["drain_limit_s"])
    for th_ in threads:
        th_.join(timeout=max(0.0, limit - time.perf_counter()))
    alive = sum(1 for th_ in threads if th_.is_alive())

    with lock:
        all_recs = [dict(r) for r in recs]
    sent = [r for r in all_recs if "send_t" in r and r["send_t"] < t_close]
    failed = [r for r in sent if "done_t" in r and not ok_request(r)]
    check = check_sample(
        served, all_recs, prompts, int(traffic["check"]["requests"]),
        float(traffic["check"]["logit_tolerance"]),
        trafficgen.rng_for(ctx["seed"], "check"))
    return {
        "t_open": t_open, "t_close": t_close, "window_s": t_close - t_open,
        "attempted": len(sent), "failed": len(failed) + alive,
        "correct_parts": {"streams_complete": not failed and not alive,
                          "reference_sample": check["ok"]},
        "check": check, "records": all_recs, "chunk": chunk,
        "counters": {"before": before, "after": after,
                     "polled": poller.samples},
        "tracer": tracer,
        "realised": dict(plan["realised"], engine_at_open=before,
                         engine_at_close=after),
        "phases": served.phases,
    }


def _filled(served: Served, slots: int, min_free: int) -> bool:
    """Every slot busy, or the pool too full to admit one more request."""
    s = served.engine_stats()
    return (s.get("slots_busy", 0) >= slots
            or s["kv_blocks_free"] + s["kv_blocks_cached"] < min_free)
