"""The served path, as a user calls it: ``serve.run(llm_deployment(...))``
and a handle, one replica on one chip, in this process (it holds the chip).
Shared by the closed-loop and the session drivers."""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List

from benchmark.drivers import common
from benchmark.readers.client import ok_request


class Served:
    def __init__(self, ctx: Dict):
        import jax

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.controller import get_or_create_controller
        from ray_tpu.serve.llm import llm_deployment

        self.phases = {"start": time.perf_counter()}
        eng = ctx["traffic"]["engine"]
        self.cfg = common.model_config(ctx["config"], ctx["rehearse"])
        init = common.resolve(ctx["config"]["init"])
        # Weights: one jitted call on the device from the seed, in the type
        # the program stores them in. The replica is a thread of this
        # process, so it is handed the same arrays.
        self.params = jax.jit(lambda key: init(self.cfg, key))(
            jax.random.key(common.seed32(ctx["seed"])))
        jax.block_until_ready(self.params)
        self.phases["weights"] = time.perf_counter()
        sysconf = dict(eng.get("system_config", {}))
        sysconf.setdefault("object_spilling_dir",
                           os.path.join(ctx["scratch_dir"], "spill"))
        if ctx["rehearse"]:
            sysconf["serve_paged_attention_kernel"] = "interpret"
            ray_tpu.init(resources={"TPU": 1.0}, system_config=sysconf)
        else:
            ray_tpu.init(system_config=sysconf)
        self._ray, self._serve = ray_tpu, serve
        LM = llm_deployment(
            self.cfg, lambda: self.params, name="LM", slots=int(eng["slots"]),
            chunk=int(eng["chunk"]), max_queue=int(eng["max_queue"]),
            num_replicas=1, ray_actor_options={"num_tpus": 1})
        self.handle = serve.run(LM.bind())
        _, table = ray_tpu.get(get_or_create_controller().get_snapshot.remote())
        self.replica = table["LM"]["replicas"][0]
        # A replica answers once its __init__ (weights and warm-up) is done.
        ray_tpu.get(self.replica.handle_request.remote("describe"),
                    timeout=1500.0)
        self.chunk = int(eng["chunk"])
        self.phases["deployed_and_warm"] = time.perf_counter()

    def engine_stats(self) -> Dict[str, float]:
        return self._ray.get(self.replica.get_metrics.remote(), timeout=60.0)

    def queued_phase(self) -> Dict[str, float]:
        """Sum and count of the engine's queued phase (submit -> admission),
        as the program's TTFT histogram holds them today."""
        from ray_tpu.core.metrics_export import serve_ttft_hist

        snap = serve_ttft_hist()._snapshot()
        for key, (_buckets, total, count) in snap["samples"]:
            if dict(key).get("phase") == "queued":
                return {"sum_s": float(total), "count": float(count)}
        return {"sum_s": 0.0, "count": 0.0}

    def stream(self, prompt: List[int], max_new: int, rec: Dict) -> None:
        """One request through the handle; fills ``rec`` with host times."""
        rec["send_t"] = time.perf_counter()
        rec["prompt_tokens"] = len(prompt)
        rec["asked"] = max_new
        times, toks, last = [], [], None
        rec["times"], rec["tokens"] = times, toks   # filled as items arrive
        try:
            for item in self.handle.options(stream=True).remote(
                    {"prompt_ids": prompt, "max_new_tokens": max_new,
                     "temperature": 0.0}):
                times.append(time.perf_counter())
                toks.append(int(item["token"]))
                last = item
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec["finish_reason"] = (last or {}).get("finish_reason")
        rec["engine_ttft_s"] = (last or {}).get("ttft_s")
        rec["done_t"] = time.perf_counter()

    def close(self) -> None:
        self._serve.shutdown()
        self._ray.shutdown()


class StatsPoller:
    """Polls the engine's counters from the side while the window runs."""

    def __init__(self, served: Served, period_s: float = 0.25):
        self.served, self.period = served, period_s
        self.samples: List[Dict] = []
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._run, name="bench-poll",
                                    daemon=True)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                s = self.served.engine_stats()
                s["t"] = time.perf_counter()
                self.samples.append(s)
            except Exception:  # noqa: BLE001 — a missed poll is not a fault
                pass
            self._stop.wait(self.period)

    def start(self) -> None:
        self._th.start()

    def stop(self) -> None:
        self._stop.set()
        self._th.join(timeout=5.0)


def check_sample(served: Served, recs: List[Dict], prompts, n: int,
                 tol: float, rng) -> Dict:
    """A seeded sample of finished requests against the plain reference.

    The prompt plus the served tokens go through the float32 reference in one
    forward pass; at each served position the served token's reference logit
    must be within ``tol`` of that position's largest. Logits and not tokens:
    with random weights the largest logit changes hands on rounding, and a
    bf16 forward pass through 24 layers differs from float32 by a few
    hundredths of a logit. A token sampled from the wrong row, a cache page
    read from the wrong block or a dropped layer puts the served token
    whole logits away. ``tol`` and its reason are in the traffic file.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import gpt2_plain as ref

    done = [i for i, r in enumerate(recs) if ok_request(r)]
    if not done:
        return {"checked": 0, "worst_gap": None, "ok": False}
    pick = [done[int(i)] for i in rng.permutation(len(done))[:n]]
    w = ref.from_program_params(served.params)
    T = served.cfg.max_seq_len
    vocab, heads = served.cfg.vocab_size, served.cfg.n_heads
    fwd = jax.jit(lambda w, t: ref.forward(w, t, heads)[0, :, :vocab])
    worst = 0.0
    for i in pick:
        seq = list(prompts[i]) + list(recs[i]["tokens"])
        padded = np.zeros((1, T), np.int32)
        padded[0, :len(seq)] = seq[:T]
        logits = np.asarray(fwd(w, jnp.asarray(padded)))
        p = len(prompts[i])
        for j, tok in enumerate(recs[i]["tokens"]):
            row = logits[p + j - 1]          # the row that predicts position p+j
            worst = max(worst, float(row.max() - row[tok]))
    return {"checked": len(pick), "worst_gap": worst, "ok": worst <= tol}
