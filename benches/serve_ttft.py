"""Serve p50 TTFT + decode-rate benchmark (north-star metric #3).

A KV-cache LLM replica (``serve/llm.py``: bucketed prefill + cached decode)
served through the full data plane (handle → pow-2 router → replica actor),
measuring time-to-first-token and steady-state decode tokens/s of streaming
generate calls. Runs on whatever device is present (real TPU chip under the
driver; CPU elsewhere).

Prints one JSON line: {"metric": "serve_p50_ttft_ms", ...}
"""

from __future__ import annotations

import json
import time

import numpy as np


def main():
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import llm_deployment

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    cfg = (
        transformer.gpt2_small(max_seq_len=256)
        if on_tpu
        else transformer.tiny(max_seq_len=64)
    )

    LM = llm_deployment(
        cfg,
        lambda: transformer.init_params(cfg, jax.random.key(0)),
        name="LM",
        max_ongoing_requests=4,
    )

    ray_tpu.init()
    handle = serve.run(LM.bind())

    # measure TTFT + decode rate over sequential requests
    ttfts, decode_tps = [], 0.0
    n_new = 16 if on_tpu else 4
    for _ in range(20):
        t0 = time.perf_counter()
        stream = iter(handle.options(stream=True).remote(
            {"prompt_len": 16, "max_new_tokens": n_new}))
        next(stream)
        ttfts.append((time.perf_counter() - t0) * 1000)
        for item in stream:
            decode_tps = item["decode_tps"]
    p50 = float(np.percentile(ttfts, 50))
    p99 = float(np.percentile(ttfts, 99))

    # Engine-side numbers, without the handle → router → replica hops the
    # e2e p50 above includes.
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.models import transformer as _t
    probe = LLMEngine(_t.init_params(cfg, jax.random.key(0)), cfg)
    probe.warmup()
    dev = probe.device_metrics(prompt_len=16)

    print(
        json.dumps(
            {
                "metric": "serve_p50_ttft_ms",
                "value": round(p50, 2),
                "unit": "ms",
                "p99_ms": round(p99, 2),
                "decode_tokens_per_sec_per_replica": decode_tps,
                **dev,
                "platform": "tpu" if on_tpu else "cpu",
            }
        )
    )
    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
