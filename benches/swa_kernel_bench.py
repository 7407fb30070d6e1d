"""The paged attention kernel at the shapes of ``mimo-v2-flash.swa-decode``:
64 query heads of 192 over 8 KV heads (a window layer's ring, a sink, a
window of 128) and over 4 (a full layer's pool), V heads of 128, 256 slots
at contexts of 512-4,088 tokens, and the two prefill walks over one prompt.

What it is for (ROADMAP R2): a window layer's ring is kept in blocks of
``window_block_tokens`` rows, the builder's choice. A decode step copies the
window's blocks and at most one more, so a small block reads fewer rows it
does not need and starts more copies. Host-clock milliseconds a call over
``--iters`` calls (a call of 256 slots is a millisecond and more: the
dispatch is under it), with the bytes a call HAD to read over the HBM peak
beside it. On a machine with no accelerator it prints nothing.

    python benches/swa_kernel_bench.py [--slots 256] [--iters 20]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.paged_attention import paged_attention

H, DK, DV, WINDOW = 64, 192, 128, 128
HBM = 819e9


def _ms(fn, *args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if jax.default_backend() == "cpu":
        return 1
    S = a.slots
    rng = np.random.default_rng(a.seed)
    key = jax.random.key(a.seed)
    lengths = jnp.asarray(rng.integers(512, 4088, S), jnp.int32)
    q = jax.random.normal(key, (S, 1, H, DK), jnp.bfloat16)
    sinks = 6.0 + jax.random.normal(key, (H,), jnp.float32)

    # a window layer's decode step over rings of several block sizes
    for rb in (16, 32, 64, 128):
        blocks = WINDOW // rb + 1
        k = jax.random.normal(key, (1, S * blocks, rb, 8 * DK), jnp.bfloat16)
        v = jax.random.normal(key, (1, S * blocks, rb, 8 * DV), jnp.bfloat16)
        tables = (jnp.arange(S)[:, None] * blocks
                  + jnp.arange(blocks)[None]).astype(jnp.int32)
        fn = jax.jit(lambda q, k, v, t, ln, s: paged_attention(
            q, k, v, t, ln, 0, window=WINDOW, sinks=s))
        ms = _ms(fn, q, k, v, tables, lengths, sinks, iters=a.iters)
        need = S * WINDOW * 8 * (DK + DV) * 2
        print(json.dumps({"kernel": "window_decode", "ring_block": rb,
                          "ring_rows": blocks * rb, "ms": ms,
                          "need_MB": need / 1e6,
                          "roofline_pct": 100 * need / HBM / (ms / 1e3)}),
              flush=True)

    # a full layer's decode step over the paged pool
    nb = 256
    k = jax.random.normal(key, (1, S * nb + 1, 16, 4 * DK), jnp.bfloat16)
    v = jax.random.normal(key, (1, S * nb + 1, 16, 4 * DV), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(S * nb).reshape(S, nb), jnp.int32)
    fn = jax.jit(lambda q, k, v, t, ln: paged_attention(q, k, v, t, ln, 0))
    ms = _ms(fn, q, k, v, tables, lengths, iters=a.iters)
    need = int(jnp.sum(lengths + 1)) * 4 * (DK + DV) * 2
    print(json.dumps({"kernel": "full_decode", "ms": ms, "need_MB": need / 1e6,
                      "roofline_pct": 100 * need / HBM / (ms / 1e3)}),
          flush=True)

    # the two prefill walks over one prompt, from position 0
    for T in (2048, 4096):
        qp = jax.random.normal(key, (1, T, H, DK), jnp.bfloat16)
        zero = jnp.zeros((1,), jnp.int32)
        kw = jax.random.normal(key, (1, T // 128, 128, 8 * DK), jnp.bfloat16)
        vw = jax.random.normal(key, (1, T // 128, 128, 8 * DV), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v, t, ln, s: paged_attention(
            q, k, v, t, ln, 0, window=WINDOW, sinks=s))
        ms = _ms(fn, qp, kw, vw, jnp.arange(T // 128)[None].astype(jnp.int32),
                 zero, sinks, iters=a.iters)
        flops = 2 * (WINDOW * (WINDOW + 1) // 2 + (T - WINDOW) * WINDOW) * H * (DK + DV)
        print(json.dumps({"kernel": "window_prefill", "tokens": T, "ms": ms,
                          "mfu_pct": 100 * flops / 197e12 / (ms / 1e3)}),
              flush=True)
        fn = jax.jit(lambda q, k, v, t, ln: paged_attention(q, k, v, t, ln, 0))
        ms = _ms(fn, qp, k, v, tables[:1], zero, iters=a.iters)
        flops = 2 * (T * (T + 1) // 2) * H * (DK + DV)
        print(json.dumps({"kernel": "full_prefill", "tokens": T, "ms": ms,
                          "mfu_pct": 100 * flops / 197e12 / (ms / 1e3)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
