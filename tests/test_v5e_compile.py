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
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# An optimized module's Pallas calls: ``%<name>.N = <first output shape>...
# custom-call(...), custom_call_target="tpu_custom_call"``. The profiler names
# a device operation by this same text, and the benchmark's kernel metrics
# match ``<name>:custom-call:<shape>`` in it.
_KERNEL = re.compile(r"%([\w\-]+?)(?:\.\d+)* = \(?(\w+\[[\d,]*\])[^\n]*"
                     r"custom_call_target=\"tpu_custom_call\"")


def compile_all() -> dict:
    """Child side: {"skip": reason} or {"programs": {name: "ok" | error},
    "kernels": {name: [[instruction name, first output shape], ...]}}."""
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
    programs, kernels = {}, {}

    def attempt(name, lower):
        try:
            text = lower().compile().as_text()
            kernels[name] = sorted(set(_KERNEL.findall(text)))
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
    return {"programs": programs, "kernels": kernels}


@pytest.fixture(scope="module")
def verdict():
    """One child process compiles everything; both tests read its verdict."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    child = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=env, capture_output=True, text=True,
                           timeout=170)
    assert child.returncode == 0, child.stderr[-3000:]
    out = json.loads(child.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(out["skip"])
    return out


def test_kernels_and_sharded_train_step_compile_for_v5e(verdict):
    programs = verdict["programs"]
    assert {"flash_fwd_bwd", "paged_decode", "paged_prefill_1024",
            "train_step_data4", "train_step_data2_tensor2"} <= set(programs)
    refused = {n: v for n, v in programs.items() if v != "ok"}
    assert not refused, json.dumps(refused, indent=1)


def test_kernels_carry_stable_names_and_unchanged_shapes(verdict):
    """Each Pallas call is named for what it is, whatever jit, scan, vjp or
    remat scope calls it, and its custom-call still has the output shape the
    benchmark's metric files match (``:custom-call:bf16[S,H,1,D]`` for the
    decode kernel, four dimensions for prefill, three for flash)."""
    kernels = verdict["kernels"]
    [[name, shape]] = kernels["paged_decode"]
    assert name == "paged_decode_attn"
    assert re.fullmatch(r"bf16\[\d+,\d+,1,\d+\]", shape), shape
    for program in ("paged_verify", "paged_prefill_16", "paged_prefill_1024"):
        [[name, shape]] = kernels[program]
        assert name == "paged_prefill_attn"
        assert re.fullmatch(r"bf16\[\d+,\d+,\d+,\d+\]", shape), shape
    for program in ("flash_fwd_bwd", "train_step_data4",
                    "train_step_data2_tensor2"):
        found = kernels[program]
        for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            hits = [shape for name, shape in found if kernel in name]
            assert hits, (program, kernel, found)
            assert all(re.fullmatch(r"bf16\[\d+,\d+,\d+\]", s)
                       for s in hits), (program, hits)
        assert all("flash_" in name for name, _shape in found), found


if __name__ == "__main__":
    print(json.dumps(compile_all()))
