"""Flash-attention kernel vs dense oracle (interpret mode on CPU; the same
kernel compiles for real TPU — exercised by bench.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.ring_attention import reference_attention


def _qkv(b=2, l=128, h=4, d=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, l, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal, None, 64, 64, True)
        oracle = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), rtol=2e-4, atol=2e-4)

    def test_uneven_blocks(self):
        q, k, v = _qkv(l=256)
        out = flash_attention(q, k, v, True, None, 128, 64, True)
        oracle = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), rtol=2e-4, atol=2e-4)

    def test_gradients_match_dense(self):
        q, k, v = _qkv(b=1, l=64, h=2, d=16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, None, 32, 32, True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)

    def test_bf16_inputs(self):
        q, k, v = _qkv(l=64)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        out = flash_attention(q, k, v, True, None, 32, 32, True)
        assert out.dtype == jnp.bfloat16
        oracle = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(oracle, np.float32), rtol=3e-2, atol=3e-2
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_backward_kernel_matches_dense(self, causal):
        """The Pallas dq/dk/dv kernels (not dense recompute) against the
        dense-path VJP, multi-block grid both axes."""
        q, k, v = _qkv(b=2, l=128, h=2, d=32)
        g_key = jax.random.key(9)
        g = jax.random.normal(g_key, q.shape, jnp.float32)

        def flash_out(q, k, v):
            return flash_attention(q, k, v, causal, None, 32, 32, True)

        def dense_out(q, k, v):
            return reference_attention(q, k, v, causal=causal).astype(jnp.float32)

        _, vjp_f = jax.vjp(flash_out, q, k, v)
        _, vjp_d = jax.vjp(dense_out, q, k, v)
        for a, b in zip(vjp_f(g), vjp_d(g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
            )

    def test_backward_uneven_blocks(self):
        q, k, v = _qkv(b=1, l=128, h=2, d=16)
        g = jax.random.normal(jax.random.key(3), q.shape, jnp.float32)
        _, vjp_f = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None, 64, 32, True),
            q, k, v)
        _, vjp_d = jax.vjp(
            lambda q, k, v: reference_attention(q, k, v, causal=True).astype(jnp.float32),
            q, k, v)
        for a, b in zip(vjp_f(g), vjp_d(g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
            )

    def test_ragged_seq_raises(self):
        """L=192 with block 128: no padded kernel exists, and handing the
        call to the dense path would hide which implementation ran."""
        q, k, v = _qkv(b=1, l=192, h=2, d=16)
        with pytest.raises(ValueError, match="multiples of the blocks"):
            flash_attention(q, k, v, True, None, 128, 128, True)


class TestFlashUnderMesh:
    """``attn_impl="flash"`` on a mesh: the kernel runs per shard inside
    ``shard_map`` (GSPMD cannot partition a Mosaic call — on a TPU the
    unwrapped call fails at lowering; interpret mode on CPU would hide it)."""

    @staticmethod
    def _attention(spec, **cfg_kw):
        from ray_tpu.models import transformer
        from ray_tpu.parallel.mesh import MeshSpec, cpu_mesh
        from ray_tpu.parallel.sharding import ShardingRules

        cfg = transformer.tiny(n_heads=4, d_model=64, max_seq_len=128,
                               attn_impl="flash", **cfg_kw)
        mesh = cpu_mesh(MeshSpec(**spec))
        return transformer._make_attention(cfg, mesh, ShardingRules()), cfg

    @pytest.mark.parametrize("spec", [dict(data=4), dict(data=2, tensor=2)])
    def test_value_and_grad_match_dense(self, spec):
        from ray_tpu.ops.flash_attention import _dense_reference

        attention, cfg = self._attention(spec)
        q, k, v = _qkv(b=4, l=128, h=4, d=16)
        scale = 1.0 / cfg.head_dim ** 0.5

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

        dense = lambda q, k, v: _dense_reference(q, k, v, scale=scale,
                                                 causal=True)
        jaxpr = str(jax.make_jaxpr(attention)(q, k, v))
        assert "shard_map" in jaxpr and "pallas_call" in jaxpr
        np.testing.assert_allclose(np.asarray(jax.jit(attention)(q, k, v)),
                                   np.asarray(dense(q, k, v)),
                                   rtol=2e-4, atol=2e-4)
        gf = jax.jit(jax.grad(loss(attention), argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    def test_sharded_sequence_refuses_flash_and_auto_rings(self):
        from ray_tpu.models import transformer

        with pytest.raises(ValueError, match="sequence sharded 2 ways"):
            self._attention(dict(data=2, seq=2))
        # auto under the same mesh resolves to ring attention: a shard_map
        # with collectives over `seq`, no Pallas call.
        from ray_tpu.parallel.mesh import MeshSpec, cpu_mesh
        from ray_tpu.parallel.sharding import ShardingRules

        cfg = transformer.tiny(n_heads=4, d_model=64, max_seq_len=128)
        auto = transformer._make_attention(
            cfg, cpu_mesh(MeshSpec(data=2, seq=2)), ShardingRules())
        jaxpr = str(jax.make_jaxpr(auto)(*_qkv(b=4, l=128, h=4, d=16)))
        assert "ppermute" in jaxpr and "pallas_call" not in jaxpr
