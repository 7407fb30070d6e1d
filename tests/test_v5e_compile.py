"""Compile for a TPU v5e without one.

libtpu can describe a ``v5e:2x2`` slice it is not attached to
(``jax.experimental.topologies``), and XLA:TPU and Mosaic will lower and
compile for those devices. That is enough to catch what CPU tests cannot:
interpret mode turns a Pallas kernel into plain HLO, which GSPMD partitions
happily and which has no VMEM, so a kernel that overflows the chip's 16 MB of
scoped VMEM, or a Mosaic call left for GSPMD to partition, passes every
interpret-mode test and fails on the first chip.

The compiles run in ONE child process (``python tests/test_v5e_compile.py``
prints a verdict per program — a builder can run it by hand): the test
process has JAX pinned to its CPU configuration, and two processes loading
libtpu at once collide on its lock file.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_all() -> dict:
    """Child side: {"skip": reason} or {"programs": {name: "ok" | error}}."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import SingleDeviceSharding

    try:
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu").devices
    except Exception as e:  # noqa: BLE001 — no libtpu, or it gives no topology
        return {"skip": f"no compile-only v5e topology: {type(e).__name__}: "
                        f"{str(e)[:300]}"}

    from ray_tpu.models import transformer
    from ray_tpu.models.training import make_train_step
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.paged_attention import paged_attention
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.serve.llm import _default_buckets

    # GPT-2-124M's width; the train step's depth is cut (compile time), the
    # refusals this test exists for do not depend on it.
    cfg = transformer.gpt2_small(max_seq_len=1024, n_layers=2, remat=True)
    ctx, H, D, bt, slots = cfg.max_seq_len, cfg.n_heads, cfg.head_dim, 16, 8
    one = SingleDeviceSharding(devices[0])
    programs = {}

    def attempt(name, lower):
        try:
            lower().compile()
            programs[name] = "ok"
        except Exception as e:  # noqa: BLE001 — the verdict IS the result
            programs[name] = f"{type(e).__name__}: {str(e)[:600]}"

    def arr(shape, dtype=jnp.bfloat16, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    qkv = arr((2, ctx, H, D))
    attempt("flash_fwd_bwd", lambda: jax.jit(jax.value_and_grad(
        lambda q, k, v: flash_attention(
            q, k, v, True, None, 512, 512, False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(qkv, qkv, qkv))

    nb_seq = ctx // bt
    pool = arr((2 * slots * nb_seq + 1, bt, H, D))
    cases = [("paged_decode", slots, 1), ("paged_verify", slots, 5)]
    cases += [(f"paged_prefill_{b}", 1, b) for b in _default_buckets(ctx)]
    for name, s, t in cases:
        attempt(name, lambda s=s, t=t: jax.jit(paged_attention).lower(
            arr((s, t, H, D)), pool, pool, arr((s, nb_seq), jnp.int32),
            arr((s,), jnp.int32)))

    rules = ShardingRules()
    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    for name, spec in [("train_step_data4", MeshSpec(data=4)),
                       ("train_step_data2_tensor2", MeshSpec(data=2, tensor=2))]:
        mesh = make_mesh(spec, devices=devices)
        bundle = make_train_step(
            loss_fn=lambda p, b, m=mesh: transformer.lm_loss(
                p, b, cfg, mesh=m, rules=rules),
            init_params_fn=lambda key: transformer.init_params(cfg, key),
            logical_params=transformer.logical_axes(cfg),
            mesh=mesh, rules=rules, optimizer=optimizer,
            batch_logical=("batch", None))
        p_shape = jax.eval_shape(
            lambda key: transformer.init_params(cfg, key), jax.random.key(0))
        o_shape = jax.eval_shape(optimizer.init, p_shape)
        placed = lambda tree, sh: jax.tree.map(  # noqa: E731
            lambda x, s: arr(x.shape, x.dtype, s), tree, sh)
        attempt(name, lambda b=bundle, p=p_shape, o=o_shape: b.step.lower(
            placed(p, b.param_shardings), placed(o, b.opt_shardings),
            {"tokens": arr((16, ctx), jnp.int32, b.batch_sharding)}))
    return {"programs": programs}


def test_kernels_and_sharded_train_step_compile_for_v5e():
    import pytest

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    child = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=env, capture_output=True, text=True,
                           timeout=170)
    assert child.returncode == 0, child.stderr[-3000:]
    verdict = json.loads(child.stdout.strip().splitlines()[-1])
    if "skip" in verdict:
        pytest.skip(verdict["skip"])
    programs = verdict["programs"]
    assert {"flash_fwd_bwd", "paged_decode", "paged_prefill_1024",
            "train_step_data4", "train_step_data2_tensor2"} <= set(programs)
    refused = {n: v for n, v in programs.items() if v != "ok"}
    assert not refused, json.dumps(refused, indent=1)


if __name__ == "__main__":
    print(json.dumps(compile_all()))
