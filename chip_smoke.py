#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, holding the chip(s) from start to end, drives the two main paths
through the entry points a user calls, at GPT-2-124M's full width (12 layers,
d_model 768, 12 heads, vocab 50257, context 1024, bf16), random weights from
a seed:

- *kernels*: ``flash_attention`` forward and gradients against its dense
  reference (at the model's shape under 512-blocks and at the shape
  gpt2-medium's train step hands it, one q block a head), ``paged_attention`` (decode, verify, prefill at every bucket,
  and one bucket five eighths full with the walk handed the count of real rows)
  against its gather reference, on random inputs at the model's shapes, and
  ``latent_paged_attention`` against its own at the two latent cells' shapes
  (64 heads on 640-lane rows; 128 slots x 64 table entries, 96 x 192);
- *train*: ``make_mesh`` over every local chip -> ``make_train_step`` with
  ``transformer.lm_loss`` (the path of ``bench.py``): a few adamw steps on one
  batch, the loss must fall and the lowered step must hold the Mosaic kernels.
  On more than one chip, the same global batch also runs on one chip and on
  each mesh layout, and the first-step losses must agree;
- *serve*: ``ray_tpu.init()`` -> ``serve.run(llm_deployment(...))`` with one
  replica per chip -> concurrent streaming requests covering every prompt
  bucket, a shared prefix and a sampled request.

Stdout is two JSON lines. The first is the report: versions, compile-cache
directory and per-phase detail. The last is the verdict and nothing else,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it. The exit code is 0 only if every phase passed.
Where JAX finds no accelerator it exits non-zero and prints no result. Any
time it prints is informational: this is not a benchmark.

``--rehearse [N]`` runs the same code on N virtual CPU devices at a tiny size
with the kernels in interpret mode. It proves control flow before chip time is
spent, says ``"platform": "cpu"``, and is never a chip result.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import time
import traceback

SEED = 0
REL_TOL = 2e-2        # bf16: max|got - want| / max|want|, vs a full-precision oracle
LOSS_REL_TOL = 5e-3   # first-step loss, one chip vs a mesh layout
SLOTS, CHUNK = 8, 8


def _rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def phase_kernels(cfg, interpret: bool) -> dict:
    """Both Pallas kernels against their references at the model's shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.core.config import config as knobs
    from ray_tpu.ops.flash_attention import _dense_reference, flash_attention
    from ray_tpu.ops.paged_attention import (latent_paged_attention,
                                             latent_paged_attention_reference,
                                             paged_attention,
                                             paged_attention_append,
                                             paged_attention_reference)
    from ray_tpu.serve.llm import _default_buckets

    ctx, H, D, dt = cfg.max_seq_len, cfg.n_heads, cfg.head_dim, cfg.dtype
    errs = {}

    # The model's shape under 512-blocks (two q blocks a head: the looped
    # walk), and the shape gpt2-medium's train step hands the kernels (16
    # sequences of 16 heads, one q block a head: no loop at all).
    step_shape = (1, ctx, H, D) if interpret else (16, 1024, 16, 64)
    for tag, shape, blk in (("flash", (2, ctx, H, D), min(512, ctx)),
                            ("flash_step", step_shape, step_shape[1])):
        q, k, v, g = (jax.random.normal(kk, shape, dt)
                      for kk in jax.random.split(jax.random.key(SEED), 4))
        scale = shape[3] ** -0.5

        def out_and_grads(attn):
            def f(q, k, v):
                out, vjp = jax.vjp(attn, q, k, v)
                return (out,) + vjp(g)
            return jax.jit(f)(q, k, v)

        got = out_and_grads(lambda q, k, v: flash_attention(
            q, k, v, True, scale, blk, blk, interpret))
        with jax.default_matmul_precision("highest"):
            want = out_and_grads(lambda q, k, v: _dense_reference(
                q, k, v, scale=scale, causal=True))
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            errs[f"{tag}_{name}"] = _rel_err(a, b)

    bt = int(knobs().serve_kv_block_tokens)
    nb_seq = ctx // bt
    pool_blocks = 2 * SLOTS * nb_seq + 1          # the engine's own auto size
    rng = np.random.default_rng(SEED)
    # One layer's pool in the engine's layout, heads folded into the lanes.
    k_pool, v_pool = (jnp.asarray(
        rng.standard_normal((1, pool_blocks, bt, H * D), np.float32), dt)
        for _ in range(2))
    # Every slot owns a shuffled chain over its whole table (block 0 = trash).
    tables = jnp.asarray(rng.permutation(np.arange(1, pool_blocks))
                         [:SLOTS * nb_seq].reshape(SLOTS, nb_seq), jnp.int32)
    kernel = jax.jit(lambda *a, **kw: paged_attention(
        *a, interpret=interpret, **kw))
    oracle = jax.jit(paged_attention_reference)

    def real_rows_err(out, want, real):
        """``_rel_err`` over a prefill's first ``real`` query rows, the walk
        handed that count (``queries``); 1.0 unless every row behind them is
        zeros, which is what the walk leaves of a pad row."""
        if bool(np.asarray(out[:, real:], np.float32).any()):
            return 1.0
        return _rel_err(out[:, :real], want[:, :real])

    def paged_case(name, t_tokens, lengths, real=None):
        n = len(lengths)
        qq = jnp.asarray(rng.standard_normal((n, t_tokens, H, D), np.float32), dt)
        ops = (qq, k_pool, v_pool, tables[:n],
               jnp.asarray(lengths, jnp.int32), 0)
        with jax.default_matmul_precision("highest"):  # read at trace time
            want = oracle(*ops)
        if real is None:
            errs[name] = _rel_err(kernel(*ops), want)
        else:
            errs[name] = real_rows_err(
                kernel(*ops, queries=jnp.full((n,), real, jnp.int32)), want,
                real)

    appending = jax.jit(lambda *a: paged_attention_append(
        *a, interpret=interpret))

    def append_case(name, lengths):
        """The decode step's call that takes the new rows as operands: its
        output against the oracle over a pool they were scattered into, and
        both pools against that scatter, the trash block aside (a parked
        slot, length None, and one at table capacity write nothing; the
        scatter sent their rows to block 0)."""
        n = len(lengths)
        qq, k_row, v_row = (jnp.asarray(rng.standard_normal(shape, np.float32), dt)
                            for shape in ((n, 1, H, D), (n, H * D), (n, H * D)))
        parked = np.asarray([ln is None for ln in lengths])
        lens = np.asarray([ln or 0 for ln in lengths])
        tbl = np.where(parked[:, None], 0, np.asarray(tables[:n]))
        pos = np.minimum(lens, ctx - 1)
        blk = np.where(lens < ctx, tbl[np.arange(n), pos // bt], 0)
        k_ref, v_ref = (pool.at[0, blk, pos % bt].set(row) for pool, row
                        in ((k_pool, k_row), (v_pool, v_row)))
        ops = (jnp.asarray(tbl), jnp.asarray(lens, jnp.int32), 0)
        with jax.default_matmul_precision("highest"):
            want = oracle(qq, k_ref, v_ref, *ops)
        out, k_new, v_new = appending(qq, k_row, v_row, k_pool, v_pool, *ops)
        live = ~parked & (lens < ctx)
        errs[name] = _rel_err(out[live], want[live])
        errs[name + "_pools"] = float(
            bool(np.asarray(out, np.float32)[parked].any()) or not all(
                bool(jnp.array_equal(new[:, 1:], ref[:, 1:]))
                for new, ref in ((k_new, k_ref), (v_new, v_ref))))

    # around a block's edge and around a group of blocks' (128 positions)
    paged_case("paged_decode_t1", 1,
               [0, bt - 1, bt, 127, 128, 129, ctx // 2, ctx - 1])
    append_case("paged_decode_append",
                [0, bt - 1, bt, 127, 128, 129, ctx // 2, ctx - 1])
    append_case("paged_decode_append_idle", [ctx, None, bt + 1, None, ctx - 1])
    paged_case("paged_verify_t5", 5,
               [0, 3, bt - 1, bt, 5 * bt + 4, ctx // 2, ctx - 6, ctx - 5])
    for b in _default_buckets(ctx):
        paged_case(f"paged_prefill_{b}", b, [0])
    paged_case("paged_prefill_prefix_hit", ctx // 4, [3 * bt])
    # a prompt that fills its bucket to five eighths: the walk is handed the
    # count of real rows and skips the tiles of pad rows alone
    paged_case("paged_prefill_half_filled", ctx, [0], real=ctx * 5 // 8 - 3)

    # The latent kernel at the shapes of longcat-flash-omni.moe-decode and
    # kimi-k2.5.agent-decode: slots, table entries, a prefill bucket; the
    # second of two sublayers, contexts spread over the table, a block's
    # and a lane row's edges and the table's end among them.
    heads, width, values = 64, 640, 512
    cells = {"longcat": (128, 64, 1024), "kimi": (96, 192, 2048)}
    if interpret:
        cells = {"longcat": (4, 8, 32), "kimi": (3, 12, 40)}
    latent = dict(value_lanes=values, scale=width ** -0.5)
    latent_kernel = jax.jit(lambda *a, **kw: latent_paged_attention(
        *a, 1, interpret=interpret, **latent, **kw))
    latent_oracle = jax.jit(lambda *a: latent_paged_attention_reference(
        *a, 1, **latent))
    for cell, (slots, nb, bucket) in cells.items():
        kp, kq = jax.random.split(jax.random.key(SEED + slots))
        pool = jax.random.normal(kp, (2, slots * nb + 1, bt, width), dt)
        chains = jnp.asarray(rng.permutation(np.arange(1, slots * nb + 1))
                             .reshape(slots, nb), jnp.int32)
        spread = rng.integers(0, nb * bt, slots)
        edges = np.minimum([0, bt - 1, 129, nb * bt - 1], nb * bt - 1)[:slots]
        spread[:len(edges)] = edges
        for name, t_tokens, lengths in (
                (f"latent_decode_{cell}", 1, spread),
                (f"latent_prefill_{cell}_{bucket}", bucket, [3 * bt])):
            n = len(lengths)
            qq = jax.random.normal(kq, (n, t_tokens, heads, width), dt)
            ops = (qq, pool, chains[:n], jnp.asarray(lengths, jnp.int32))
            with jax.default_matmul_precision("highest"):
                want = latent_oracle(*ops)
            errs[name] = _rel_err(latent_kernel(*ops), want)
            if t_tokens > 1:
                real = t_tokens * 5 // 8 - 3
                errs[name + "_half_filled"] = real_rows_err(
                    latent_kernel(*ops, queries=jnp.full((n,), real,
                                                         jnp.int32)),
                    want, real)

    worst = max(errs, key=errs.get)
    return {"ok": all(e <= REL_TOL for e in errs.values()),
            "rel_tol": REL_TOL, "worst": [worst, errs[worst]],
            "rel_err": {k_: round(e, 5) for k_, e in errs.items()}}


def _train_run(cfg, devices, spec, global_batch: int, steps: int) -> dict:
    """``steps`` adamw steps on one fixed batch under ``spec``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import transformer
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import make_mesh, mesh_shape
    from ray_tpu.parallel.sharding import ShardingRules

    mesh = make_mesh(spec, devices=devices)
    rules = ShardingRules()
    bundle = make_train_step(
        loss_fn=lambda p, b: transformer.lm_loss(p, b, cfg, mesh=mesh, rules=rules),
        init_params_fn=lambda key: transformer.init_params(cfg, key),
        logical_params=transformer.logical_axes(cfg),
        mesh=mesh,
        rules=rules,
        optimizer=optax.adamw(3e-4, weight_decay=0.1),
        batch_logical=("batch", None),
    )
    params, opt_state = bundle.init(jax.random.key(SEED))
    tokens = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (global_batch, cfg.max_seq_len))
    batch = {"tokens": jax.device_put(jnp.asarray(tokens, jnp.int32),
                                      bundle.batch_sharding)}
    # The Mosaic kernels, counted in what jit hands the compiler: a kernel in
    # interpret mode, or the dense fallback, leaves no custom call.
    custom_calls = bundle.step.lower(params, opt_state, batch).as_text().count(
        "tpu_custom_call")

    t0 = time.perf_counter()
    params, opt_state, metrics = bundle.step(params, opt_state, batch)
    jax.block_until_ready(metrics)
    first_step_s = time.perf_counter() - t0
    losses = [metrics["loss"]]
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        params, opt_state, metrics = bundle.step(params, opt_state, batch)
        losses.append(metrics["loss"])
    jax.block_until_ready((params, losses))
    steady_s = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    out = {
        "mesh": {a: s for a, s in mesh_shape(mesh).items() if s > 1},
        "devices": len(devices), "global_batch": global_batch, "steps": steps,
        "loss_first": round(losses[0], 6), "loss_last": round(losses[-1], 6),
        "finite": all(x == x and abs(x) != float("inf") for x in losses),
        "tpu_custom_calls": custom_calls,
        "compile_and_first_step_s": round(first_step_s, 2),
    }
    if steps > 1:
        out["informational_step_s"] = round(steady_s / (steps - 1), 4)
    return out


def phase_train(cfg, devices, on_tpu: bool, batch_per_chip: int) -> dict:
    from ray_tpu.parallel.mesh import MeshSpec

    n = len(devices)

    def kernels_ok(run):
        return run["tpu_custom_calls"] >= 3 if on_tpu else True

    main = _train_run(cfg, devices, MeshSpec(data=-1), batch_per_chip * n, 6)
    ok = (main["finite"] and main["loss_last"] < main["loss_first"]
          and kernels_ok(main))
    out = {"data_parallel": main}
    if n > 1:
        # One global batch, one chip vs every layout of all the chips: the
        # per-shard kernels and the collectives must not change the loss.
        specs = [MeshSpec(data=n)]
        if n % 2 == 0:
            specs.append(MeshSpec(data=n // 2, tensor=2))
        g = batch_per_chip if batch_per_chip % n == 0 else n
        one = _train_run(cfg, devices[:1], MeshSpec(data=1), g, 1)
        out["one_chip"] = one
        out["layouts"] = []
        for spec in specs:
            run = _train_run(cfg, devices, spec, g, 1)
            run["loss_first_vs_one_chip_rel"] = round(
                abs(run["loss_first"] - one["loss_first"])
                / abs(one["loss_first"]), 6)
            out["layouts"].append(run)
            ok = (ok and run["finite"] and kernels_ok(run)
                  and run["loss_first_vs_one_chip_rel"] <= LOSS_REL_TOL)
        ok = ok and one["finite"] and kernels_ok(one)
    out["ok"] = bool(ok)
    return out


def _requests(cfg, buckets, n_replicas: int):
    """(name, prompt, max_new, temperature, expected bucket) — one prompt per
    bucket (3/4 of it, so the last is > half the context), one sampled, and
    ``n_replicas + 1`` prompts sharing a 5-block prefix: sent one after the
    other, two of them meet on one replica wherever the router sends them."""
    import numpy as np

    rng = np.random.default_rng(SEED + 1)

    def prompt(n):
        return [int(t) for t in rng.integers(1, cfg.vocab_size, n)]

    concurrent_reqs = [(f"bucket_{b}", prompt(b * 3 // 4), 16, 0.0, b)
                       for b in buckets]
    concurrent_reqs.append(("sampled", prompt(12), 16, 0.8, buckets[0]))
    prefix = prompt(80)
    shared = [(f"shared_prefix_{i}", prefix + prompt(8), 8, 0.0, None)
              for i in range(n_replicas + 1)]
    return concurrent_reqs, shared


def phase_serve(cfg, n_devices: int, rehearse: bool) -> dict:
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import transformer
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import _default_buckets, llm_deployment

    buckets = _default_buckets(cfg.max_seq_len)
    if rehearse:
        # No chip to detect: grant the virtual devices by hand, and take the
        # interpreted kernel (`auto` on a CPU is the gather formulation).
        ray_tpu.init(
            resources={"TPU": float(n_devices)},
            system_config={"serve_paged_attention_kernel": "interpret"})
    else:
        ray_tpu.init()
    out: dict = {"ok": False}
    try:
        out["cluster_resources"] = {
            k_: v for k_, v in ray_tpu.cluster_resources().items()
            if k_.startswith("TPU")}
        LM = llm_deployment(
            cfg, lambda: transformer.init_params(cfg, jax.random.key(SEED)),
            name="LM", slots=SLOTS, chunk=CHUNK, num_replicas=n_devices,
            ray_actor_options={"num_tpus": 1})
        t0 = time.perf_counter()
        handle = serve.run(LM.bind())
        # A replica answers once its __init__ (params + warmup) is done.
        _, table = ray_tpu.get(get_or_create_controller().get_snapshot.remote())
        replicas = table["LM"]["replicas"]
        described = ray_tpu.get(
            [r.handle_request.remote("describe") for r in replicas],
            timeout=1000.0)
        out["deploy_and_warmup_s"] = round(time.perf_counter() - t0, 2)
        out["replicas"] = described
        out["replica_devices"] = [
            {"params": d["params_devices"], "kv_pool": d["kv_pool_devices"]}
            for d in described]
        out["attention_kernel"] = sorted(
            {d["attention_kernel"] for d in described})

        def one(req):
            name, prompt, max_new, temperature, bucket = req
            items = list(handle.options(stream=True).remote(
                {"prompt_ids": prompt, "max_new_tokens": max_new,
                 "temperature": temperature, "seed": SEED + 7}))
            tokens = [it["token"] for it in items]
            return {"name": name, "prompt_len": len(prompt), "bucket": bucket,
                    "asked": max_new, "got": len(tokens),
                    "finish_reason": items[-1].get("finish_reason") if items else None,
                    "tokens_in_vocab": all(0 <= t < cfg.vocab_size for t in tokens)}

        concurrent_reqs, shared = _requests(cfg, buckets, n_devices)
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(concurrent_reqs)) as pool:
            results = list(pool.map(one, concurrent_reqs))
        results += [one(req) for req in shared]
        out["requests_s"] = round(time.perf_counter() - t0, 2)
        out["requests"] = results

        metrics = ray_tpu.get([r.get_metrics.remote() for r in replicas],
                              timeout=60.0)
        out["kv_hit_tokens"] = [m.get("kv_hit_tokens") for m in metrics]
        out["ok"] = bool(
            len(replicas) == n_devices
            and out["cluster_resources"].get("TPU") == jax.local_device_count()
            and all(d["warmed_buckets"] == buckets for d in described)
            and all(r["got"] == r["asked"] and r["finish_reason"]
                    and r["tokens_in_vocab"] for r in results)
            and {r["bucket"] for r in results} >= set(buckets)
            and sum(h or 0 for h in out["kv_hit_tokens"]) > 0
            and out["attention_kernel"]
            == ["interpret" if rehearse else "pallas"])
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", type=int, nargs="?", const=1, default=0,
                    metavar="N", help="CPU rehearsal on N virtual devices at "
                    "a tiny size (interpret-mode kernels); not a chip result")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.rehearse}").strip()

    from ray_tpu.util.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform == "cpu" and not args.rehearse:
        sys.exit("chip_smoke: JAX found no accelerator (platform cpu); "
                 "nothing ran. `--rehearse` is the CPU rehearsal.")
    on_tpu = devices[0].platform == "tpu"

    from ray_tpu.models import transformer

    if args.rehearse:
        # 4 heads of 32: a pool row of one whole 128-lane tile, so that the
        # rehearsal's decode takes the kernel that writes its own rows
        model = lambda **kw: transformer.tiny(d_model=128, max_seq_len=1024,
                                              **kw)
        batch_per_chip = 1
    else:
        model = lambda **kw: transformer.gpt2_small(max_seq_len=1024, **kw)
        batch_per_chip = 16

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # a CPU-only install has none
        libtpu = None
    report = {
        "ok": False,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "rehearsal": bool(args.rehearse),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache_dir": cache_dir,
        "phases": {},
    }
    phases = [
        ("kernels", lambda: phase_kernels(model(), interpret=not on_tpu)),
        ("train", lambda: phase_train(
            model(attn_impl="auto", remat=True), devices, on_tpu,
            batch_per_chip)),
        ("serve", lambda: phase_serve(model(), len(devices),
                                      bool(args.rehearse))),
    ]
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            result = fn()
        except Exception as e:  # noqa: BLE001 — a failed phase fails the run
            traceback.print_exc()
            result = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
        result["wall_s"] = round(time.perf_counter() - t0, 2)
        stats = devices[0].memory_stats() or {}
        result["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        report["phases"][name] = result
        print(f"chip_smoke: {name}: {'ok' if result['ok'] else 'FAILED'} "
              f"in {result['wall_s']}s", file=sys.stderr, flush=True)
    report["wall_s"] = round(time.perf_counter() - t_all, 2)
    report["ok"] = all(p["ok"] for p in report["phases"].values())
    print(json.dumps(report), flush=True)
    # The verdict line: exactly these keys, last on stdout.
    print(json.dumps({"ok": report["ok"], "device": report["device"]}),
          flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
