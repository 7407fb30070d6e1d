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


def _rel_err(got, want):
    """chip_smoke.py's criterion: max error over max magnitude."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _f32_oracle(q, k, v, g, causal):
    """Dense attention and its VJP, every product in float32."""
    q, k, v, g = (x.astype(jnp.float32) for x in (q, k, v, g))
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda q, k, v: reference_attention(q, k, v, causal=causal),
            q, k, v)
        return (out,) + vjp(g)


class TestFlashBf16:
    """bfloat16 in, as both train cells feed it: the products take bfloat16
    operands (``p`` and ``ds`` rounded to it) and sum in float32. Forward
    AND backward against the float32 dense oracle at ``chip_smoke.py``'s
    criterion, ``REL_TOL`` 2e-2 for out, dq, dk, dv."""

    REL_TOL = 2e-2

    # (L, block_q, block_k): the blocks are upper bounds; 3 heads (an odd
    # B*H); 1,280 is the length the model's block rule hands 256-blocks;
    # (512, 256, 128) and (1024, 512, 128): the diagonal crosses kv chunks
    # INSIDE a q block; (384, 128, 384): block_k above block_q.
    @pytest.mark.parametrize("l,block_q,block_k", [
        (128, 512, 512), (384, 512, 512), (1024, 512, 512),
        (1280, 256, 256), (512, 256, 128), (1024, 512, 128),
        (384, 128, 384)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_and_backward_match_f32_oracle(self, causal, l, block_q,
                                                   block_k):
        ks = jax.random.split(jax.random.key(l + block_k), 4)
        q, k, v, g = (jax.random.normal(kk, (1, l, 3, 64), jnp.float32)
                      .astype(jnp.bfloat16) for kk in ks)
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal, None, block_q,
                                            block_k, True), q, k, v)
        got = (out,) + vjp(g)
        assert all(x.dtype == jnp.bfloat16 for x in got)
        want = _f32_oracle(q, k, v, g, causal)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            assert _rel_err(a, b) <= self.REL_TOL, (name, _rel_err(a, b))

    def test_scale_not_a_power_of_two(self):
        """D = 48: 1/sqrt(D) cannot ride the bfloat16 operand exactly, so it
        stays on the float32 scores."""
        ks = jax.random.split(jax.random.key(48), 4)
        q, k, v, g = (jax.random.normal(kk, (1, 256, 3, 48), jnp.float32)
                      .astype(jnp.bfloat16) for kk in ks)
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None, 128, 64,
                                            True), q, k, v)
        for a, b in zip((out,) + vjp(g), _f32_oracle(q, k, v, g, True)):
            assert _rel_err(a, b) <= self.REL_TOL


class TestFlashHeadsInPlace:
    """The kernels cut a head out of ``[B, H*D, L]`` by their block specs
    (PR 41): any number of heads, even or odd, any head width, and a width
    that makes ``H*D`` no multiple of 128 go the same way, with no head
    folded into the batch. Forward AND gradients against the float32 oracle
    at ``chip_smoke.py``'s criterion; two q blocks a head, the diagonal
    crossing chunks inside a block."""

    REL_TOL = 2e-2

    # (heads, head width): pairs of 64-wide heads (2, 4, 16), an odd head
    # left over (3, 5: gpt2-xl has 25), one head a lane tile (D = 128),
    # H*D = 144 and 96 (no multiple of 128), a single head.
    @pytest.mark.parametrize("h,d", [(2, 64), (4, 64), (16, 64), (3, 64),
                                     (5, 64), (2, 128), (3, 48), (3, 32),
                                     (1, 64)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_and_backward_match_f32_oracle(self, causal, h, d):
        ks = jax.random.split(jax.random.key(17 * h + d), 4)
        q, k, v, g = (jax.random.normal(kk, (2, 256, h, d), jnp.float32)
                      .astype(jnp.bfloat16) for kk in ks)
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal, None, 128, 64,
                                            True), q, k, v)
        got = (out,) + vjp(g)
        assert all(x.dtype == jnp.bfloat16 and x.shape == q.shape
                   for x in got)
        want = _f32_oracle(q, k, v, g, causal)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            assert _rel_err(a, b) <= self.REL_TOL, (name, _rel_err(a, b))

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("h", [2, 3])
    def test_spans_with_heads_side_by_side(self, monkeypatch, causal, h):
        """The spans path (``_RESIDENT_ROWS`` shrunk) at bfloat16 with the
        heads of one lane tile, and an odd one, side by side in the array."""
        from ray_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "_RESIDENT_ROWS", 128)
        assert fa._grid(512, 512, 128)[1] == 4  # spans
        ks = jax.random.split(jax.random.key(h), 4)
        q, k, v, g = (jax.random.normal(kk, (1, 512, h, 64), jnp.float32)
                      .astype(jnp.bfloat16) for kk in ks)
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal, None, 128, 64,
                                            True), q, k, v)
        want = _f32_oracle(q, k, v, g, causal)
        for name, a, b in zip(("out", "dq", "dk", "dv"), (out,) + vjp(g),
                              want):
            assert _rel_err(a, b) <= self.REL_TOL, (name, _rel_err(a, b))

    def test_neighbouring_heads_do_not_leak(self):
        """Head 0 has ``v`` all zeros, head 1 has not: head 0's output is
        zero to the bit, and so are ``dq`` and ``dk`` of head 0 (its scores
        move nothing) while its ``dv`` is not; head 1 reads as it does
        alone."""
        ks = jax.random.split(jax.random.key(41), 4)
        q, k, v, g = (jax.random.normal(kk, (1, 256, 2, 64), jnp.float32)
                      .astype(jnp.bfloat16) for kk in ks)
        v = v.at[:, :, 0, :].set(0)

        def attend(q, k, v):
            return flash_attention(q, k, v, True, None, 128, 64, True)

        out, vjp = jax.vjp(attend, q, k, v)
        dq, dk, dv = vjp(g)
        for name, x in (("out", out), ("dq", dq), ("dk", dk)):
            assert not np.asarray(x[:, :, 0, :], np.float32).any(), name
        assert np.asarray(dv[:, :, 0, :], np.float32).any()
        alone, vjp1 = jax.vjp(attend, q[:, :, 1:], k[:, :, 1:], v[:, :, 1:])
        for a, b in zip((out, dq, dk, dv), (alone,) + vjp1(g[:, :, 1:])):
            np.testing.assert_array_equal(
                np.asarray(a[:, :, 1:], np.float32),
                np.asarray(b, np.float32))

    def test_kernels_read_and_write_heads_by_positions(self):
        """What the two Pallas calls are handed: ``[B, H*D, L]`` operands
        and first outputs, three-dimensional bfloat16 (the benchmark's
        ``flash_attn_roofline`` finds the kernels by that), statistics
        ``[B, H, 1, L]`` float32; one grid step a head."""
        q = jnp.zeros((2, 256, 3, 64), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None, 256, 256,
                                            True), q, k, v)[1](q))(q, q, q)
        calls = {e.params["name"]: e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"}
        assert sorted(calls) == ["flash_bwd", "flash_fwd"]
        for name, n_in in (("flash_fwd", 3), ("flash_bwd", 4)):
            call = calls[name]
            assert list(call.params["grid_mapping"].grid) == [2, 3, 1, 1]
            for var in (*call.invars[:n_in], call.outvars[0]):
                assert var.aval.shape == (2, 3 * 64, 256), (name, var.aval)
                assert var.aval.dtype == jnp.bfloat16
        assert calls["flash_fwd"].outvars[1].aval.shape == (2, 3, 1, 256)
        assert calls["flash_fwd"].outvars[1].aval.dtype == jnp.float32


class TestFlashSpans:
    """Past ``_RESIDENT_ROWS`` keys a head's K and V no longer sit in VMEM
    whole: the kv axis goes onto the grid in spans, the forward carrying its
    statistics across them and the backward handing back one ``dq`` a span.
    The rule reads the shape alone, so the test shrinks its constant."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("l,resident", [(512, 128), (384, 128),
                                            (512, 256)])
    def test_spans_match_dense(self, monkeypatch, causal, l, resident):
        from ray_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "_RESIDENT_ROWS", resident)
        assert fa._grid(l, l, 128)[1] == l // resident  # spans
        q, k, v = _qkv(b=1, l=l, h=3, d=32, seed=l)
        g = jax.random.normal(jax.random.key(5), q.shape, jnp.float32)
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal, None, 128, 64,
                                            True), q, k, v)
        want, vjp_d = jax.vjp(
            lambda q, k, v: reference_attention(
                q, k, v, causal=causal).astype(jnp.float32), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        for a, b in zip(vjp(g), vjp_d(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)

    def test_cross_attention_spans(self, monkeypatch):
        """Not causal, fewer queries than keys, keys in two spans."""
        from ray_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "_RESIDENT_ROWS", 128)
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (1, 64, 2, 32), jnp.float32)
        k, v = (jax.random.normal(kk, (1, 256, 2, 32), jnp.float32)
                for kk in ks[1:])
        out = flash_attention(q, k, v, False, None, 64, 64, True)
        oracle = reference_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=2e-4, atol=2e-4)


class TestBlockRule:
    def test_blocks_by_shape(self):
        from ray_tpu.ops.flash_attention import _blocks

        # (q block, forward chunk, backward chunk) under no bound but the
        # rule's: one q block a head up to 1,024, else the widest multiple
        # of 128 that divides; the chunk divides the q block.
        assert _blocks(1024, 1024, 1024, 1024, True) == (1024, 512, 256)
        assert _blocks(2048, 2048, 2048, 2048, True) == (1024, 512, 256)
        assert _blocks(1280, 1280, 1280, 1280, True) == (640, 128, 128)
        assert _blocks(1152, 1152, 1152, 1152, True) == (384, 384, 128)
        assert _blocks(128, 128, 512, 512, True) == (128, 128, 128)
        # the arguments are upper bounds
        assert _blocks(1024, 1024, 512, 512, True) == (512, 512, 256)
        assert _blocks(1024, 1024, 256, 64, True) == (256, 64, 64)
        assert _blocks(256, 256, 64, 32, True) == (64, 32, 32)

    def test_causal_needs_one_length(self):
        q, k, v = _qkv(b=1, l=128, h=2, d=16)
        with pytest.raises(ValueError, match="one length"):
            flash_attention(q[:, :64], k, v, True, None, 64, 64, True)
