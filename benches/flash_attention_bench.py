"""Flash vs dense attention, forward + backward, on the real chip.

The long-context story: the Pallas kernels (blocks by the kernel's own rule
under the 512 bound handed here, O(L) memory) against
the XLA dense path (O(L²) memory) across sequence lengths (B=4, H=12, D=64,
bf16, causal). On this installation: not measured.

The kernels alone at the shapes the two train cells hand them (16 sequences
of 16 heads and 8 of 25, 1,024 positions, one q block a head), operands in
the layout the kernels take (``[B, H*D, L]``), in DEVICE milliseconds a call
read from a profiler trace: a host clock around a 0.6 ms call measures the
dispatch (PERF.md §6, PR 41, has the table this reproduces).

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

from ray_tpu.ops.flash_attention import (_blocks, _dense_reference,
                                         _flash_backward, _flash_forward,
                                         _lay, flash_attention)
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


def _device_ms(fn, *args, kernel: str, iters=20) -> float:
    """Device ms a call of the operations named ``kernel`` in ``fn``, read
    from a profiler trace with the benchmark's own reader."""
    import glob
    import shutil
    import tempfile

    from benchmark.reduce import trace

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    out_dir = tempfile.mkdtemp(prefix="flash_bench_")
    jax.profiler.start_trace(out_dir)
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    [path] = glob.glob(f"{out_dir}/plugins/profile/*/*.xplane.pb")
    seconds = trace.op_seconds(trace.load_xplane(path, ()), kernel)["seconds"]
    shutil.rmtree(out_dir, ignore_errors=True)
    return round(seconds * 1e3 / iters, 4)


def bench_train_shapes() -> None:
    """``flash_fwd`` and ``flash_bwd`` alone at the train cells' shapes."""
    for b, l, h, d in ((16, 1024, 16, 64), (8, 1024, 25, 64)):
        q, k, v, g = (_lay(jax.random.normal(kk, (b, l, h, d), jnp.bfloat16))
                      for kk in jax.random.split(jax.random.key(0), 4))
        bq, bk_fwd, bk_bwd = _blocks(l, l, l, l, True)
        kw = dict(heads=h, scale=d ** -0.5, causal=True, block_q=bq,
                  interpret=False)
        fwd = jax.jit(lambda q, k, v: _flash_forward(
            q, k, v, block_k=bk_fwd, **kw))
        o, lse = fwd(q, k, v)
        delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                        .reshape(b, h, d, l), axis=2)[:, :, None, :]
        bwd = jax.jit(lambda q, k, v, g: _flash_backward(
            q, k, v, g, lse, delta, block_k=bk_bwd, **kw))
        print(json.dumps({
            "metric": f"flash_kernels_b{b}_l{l}_h{h}_d{d}",
            "blocks": [bq, bk_fwd, bk_bwd],
            "fwd_device_ms": _device_ms(fwd, q, k, v, kernel="flash_fwd"),
            "bwd_device_ms": _device_ms(bwd, q, k, v, g, kernel="flash_bwd"),
            "platform": jax.devices()[0].platform}))


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
    if on_tpu:
        bench_train_shapes()
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
