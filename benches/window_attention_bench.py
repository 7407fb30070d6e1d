"""The windowed decode walk against the context, on the real chip.

A sliding-window layer's decode kernel (``ops/paged_attention.py:
paged_attention`` with ``window=``, over a ring a slot) must cost what the
WINDOW costs, whatever the context: the walk starts at the window's first
block. This times the kernel alone at the sizes of the cell
``trinity-large-preview.window-decode`` (64 slots, 48 query heads over 8 KV
heads of 128, a window of 4,096 in ring blocks of 128: a ring of 33 blocks)
at contexts under, at and past the window, beside the unwindowed kernel over
a paged pool of 16-token blocks at the same contexts, whose cost grows with
the context. DEVICE milliseconds a call, read from a profiler trace with the
benchmark's own reader (a host clock around a sub-millisecond call measures
the dispatch). Prints one JSON line a context; fails without a chip unless
``--quick`` (a tiny geometry, interpreted, as a CI smoke).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benches.flash_attention_bench import _device_ms  # noqa: E402
from ray_tpu.ops.paged_attention import paged_attention  # noqa: E402

ROW_BYTES = 2 * 8 * 128 * 2         # K and V, 8 KV heads of 128, bfloat16
HBM_BYTES_PER_S = 819e9             # benchmark/reduce/peaks.json, TPU v5e


def bench(slots: int, heads: int, kv_heads: int, dim: int, window: int,
          ring_block: int, contexts, interpret: bool, iters: int) -> None:
    ring = -(-window // ring_block) + 1
    dtype = jnp.float32 if interpret else jnp.bfloat16
    key = jax.random.key(0)
    q = jax.random.normal(key, (slots, 1, heads, dim), dtype)
    rings = jax.random.normal(key, (1, slots * ring, ring_block,
                                    kv_heads * dim), dtype)
    ring_tables = (jnp.arange(slots)[:, None] * ring
                   + jnp.arange(ring)[None, :]).astype(jnp.int32)
    bt, nb = 16, -(-max(contexts) // 16) + 1
    pool = jax.random.normal(key, (1, slots * nb + 1, bt, kv_heads * dim), dtype)
    tables = (1 + jnp.arange(slots)[:, None] * nb
              + jnp.arange(nb)[None, :]).astype(jnp.int32)
    windowed = jax.jit(lambda q, r, t, ln: paged_attention(
        q, r, r, t, ln, 0, window=window, interpret=interpret))
    full = jax.jit(lambda q, p, t, ln: paged_attention(
        q, p, p, t, ln, 0, interpret=interpret))
    for ctx in contexts:
        lengths = jnp.full((slots,), ctx - 1, jnp.int32)   # ctx keys with its own
        row = {"metric": f"window_decode_s{slots}_h{heads}_w{window}_ctx{ctx}",
               "platform": jax.devices()[0].platform}
        if interpret:
            jax.block_until_ready(windowed(q, rings, ring_tables, lengths))
            jax.block_until_ready(full(q, pool, tables, lengths))
        else:
            w_ms = _device_ms(windowed, q, rings, ring_tables, lengths,
                              kernel="^window_decode_attn", iters=iters)
            f_ms = _device_ms(full, q, pool, tables, lengths,
                              kernel="^paged_decode_attn", iters=iters)
            need = lambda rows: slots * rows * ROW_BYTES / HBM_BYTES_PER_S * 1e3  # noqa: E731
            row.update(window_device_ms=w_ms, full_device_ms=f_ms,
                       window_roofline=round(100 * need(min(ctx, window)) / w_ms, 1),
                       full_roofline=round(100 * need(ctx) / f_ms, 1))
        print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.quick:
        bench(2, 4, 2, 64, 16, 8, (8, 16, 40), True, 1)
        return
    if jax.devices()[0].platform == "cpu":
        raise SystemExit("window_attention_bench: no accelerator; --quick is "
                         "the CPU smoke")
    bench(64, 48, 8, 128, 4096, 128, (1024, 2048, 4096, 5200, 6144, 7680), False, 10)


if __name__ == "__main__":
    main()
