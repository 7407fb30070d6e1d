"""Flash vs dense attention, forward + backward, on the real chip.

The long-context story: the Pallas kernels (blocks by the kernel's own rule
under the 512 bound handed here, O(L) memory) against
the XLA dense path (O(L²) memory) across sequence lengths (B=4, H=12, D=64,
bf16, causal). On this installation: not measured.

Also benches the paged-attention decode kernel (block-table-native, scalar
prefetch) against the gather reference that materializes the whole
``[S, max_len, H, D]`` cache per step — the serve-engine roofline story.

Prints one JSON line per sequence length / pool geometry. ``--quick`` runs
a single tiny geometry with 1 timed iteration as a CI smoke.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.flash_attention import _dense_reference, flash_attention
from ray_tpu.ops.paged_attention import (paged_attention,
                                         paged_attention_reference)

B, H, D = 4, 12, 64


def _bench(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def bench_paged(quick: bool) -> None:
    """Paged decode attention: Pallas kernel vs gather reference.

    On CPU the kernel runs in interpret mode — absolute numbers are
    meaningless there (interpret is a correctness twin, not a perf path),
    so the gather row is the one to read; on TPU both rows are compiled
    and the speedup column is the roofline result.
    """
    on_tpu = jax.devices()[0].platform != "cpu"
    geoms = [(4, 8, 16)] if quick else (
        [(8, 16, 128), (16, 16, 128)] if on_tpu else [(4, 8, 32)])
    for S, nb_seq, bt in geoms:
        rng = np.random.default_rng(0)
        pool = S * nb_seq + 1  # + trash block 0
        q = jnp.asarray(rng.standard_normal((S, 1, H, D)), jnp.float32)
        k_pool = jnp.asarray(rng.standard_normal((1, pool, bt, H * D)),
                             jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal((1, pool, bt, H * D)),
                             jnp.float32)
        tables = jnp.asarray(
            np.arange(1, S * nb_seq + 1, dtype=np.int32).reshape(S, nb_seq))
        lengths = jnp.asarray(
            np.full((S,), nb_seq * bt - 1, dtype=np.int32))
        kern = jax.jit(lambda *a: paged_attention(*a, interpret=not on_tpu))
        ref = jax.jit(paged_attention_reference)
        iters = 1 if quick else (20 if on_tpu else 3)
        rec = {
            "metric": f"paged_attention_s{S}_ctx{nb_seq * bt}",
            "kernel_ms": round(_bench(kern, q, k_pool, v_pool, tables,
                                      lengths, 0, iters=iters), 2),
            "gather_ms": round(_bench(ref, q, k_pool, v_pool, tables,
                                      lengths, 0, iters=iters), 2),
            "kernel_mode": "pallas" if on_tpu else "interpret",
            "platform": jax.devices()[0].platform,
        }
        if on_tpu:
            rec["speedup"] = round(rec["gather_ms"] / rec["kernel_ms"], 2)
        print(json.dumps(rec))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="single tiny geometry, 1 iter — CI smoke")
    ap.add_argument("--skip-flash", action="store_true",
                    help="bench only the paged-attention rows")
    args = ap.parse_args()
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench_paged(args.quick)
    if args.skip_flash or args.quick:
        return
    on_tpu = jax.devices()[0].platform != "cpu"
    seqs = (1024, 2048, 4096) if on_tpu else (256,)
    for L in seqs:
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (B, L, H, D), jnp.bfloat16) for kk in ks)
        g = jax.random.normal(jax.random.key(9), (B, L, H, D), jnp.bfloat16)
        interp = not on_tpu

        def loss_f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, None, 512, 512, interp)
                .astype(jnp.float32) * g.astype(jnp.float32))

        def loss_d(q, k, v):
            return jnp.sum(
                _dense_reference(q, k, v, scale=D**-0.5, causal=True)
                .astype(jnp.float32) * g.astype(jnp.float32))

        fwd_f = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, True, None, 512, 512, interp))
        fwd_d = jax.jit(lambda q, k, v: _dense_reference(
            q, k, v, scale=D**-0.5, causal=True))
        grad_f = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))
        grad_d = jax.jit(jax.grad(loss_d, argnums=(0, 1, 2)))
        iters = 20 if on_tpu else 2
        rec = {
            "metric": f"flash_attention_seq{L}",
            "flash_fwd_ms": round(_bench(fwd_f, q, k, v, iters=iters), 2),
            "dense_fwd_ms": round(_bench(fwd_d, q, k, v, iters=iters), 2),
            "flash_grad_ms": round(_bench(grad_f, q, k, v, iters=iters), 2),
            "dense_grad_ms": round(_bench(grad_d, q, k, v, iters=iters), 2),
            "platform": jax.devices()[0].platform,
        }
        rec["fwd_speedup"] = round(rec["dense_fwd_ms"] / rec["flash_fwd_ms"], 2)
        rec["grad_speedup"] = round(rec["dense_grad_ms"] / rec["flash_grad_ms"], 2)
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
