"""KV-cache generation tests: incremental decode must match the full
forward (the numerical oracle), greedy determinism, streaming."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import transformer
from ray_tpu.models.generate import Generator, init_cache, _forward_cached


@pytest.fixture(scope="module")
def setup():
    cfg = transformer.tiny(max_seq_len=32, n_layers=2)
    params = transformer.init_params(cfg, jax.random.key(0))
    return cfg, params


class TestKVCache:
    def test_prefill_matches_full_forward(self, setup):
        cfg, params = setup
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
        )
        full = transformer.forward(params, tokens, cfg)
        cache = init_cache(cfg, 2)
        logits, cache = _forward_cached(params, tokens, cache, cfg, 0)
        np.testing.assert_allclose(
            np.asarray(full, np.float32), np.asarray(logits, np.float32),
            rtol=2e-4, atol=2e-4,
        )
        assert int(cache["length"]) == 16

    def test_incremental_decode_matches_full(self, setup):
        """Decoding token-by-token with the cache must give the same logits
        as running the growing sequence through the full forward."""
        cfg, params = setup
        rng = np.random.default_rng(1)
        seq = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)

        cache = init_cache(cfg, 1)
        logits, cache = _forward_cached(
            params, jnp.asarray(seq[None, :4]), cache, cfg, 0
        )
        cached_logits = [np.asarray(logits[0, -1], np.float32)]
        for i in range(4, 12):
            logits, cache = _forward_cached(
                params, jnp.asarray(seq[None, i : i + 1]), cache, cfg, i
            )
            cached_logits.append(np.asarray(logits[0, -1], np.float32))

        for i in range(4, 13):
            full = transformer.forward(params, jnp.asarray(seq[None, :i]), cfg)
            np.testing.assert_allclose(
                np.asarray(full[0, -1], np.float32),
                cached_logits[i - 4],
                rtol=3e-4, atol=3e-4,
                err_msg=f"mismatch at position {i}",
            )

    def test_greedy_generation_deterministic(self, setup):
        cfg, params = setup
        g = Generator(params, cfg, batch=1)
        out1 = g.generate([1, 2, 3], max_new_tokens=8)
        out2 = g.generate([1, 2, 3], max_new_tokens=8)
        assert out1 == out2
        assert len(out1) == 8
        assert all(0 <= t < cfg.vocab_size for t in out1)

    def test_greedy_matches_full_forward_argmax(self, setup):
        """Each greedy token must equal argmax of the full-forward logits on
        the growing sequence — the e2e oracle for the whole decode path."""
        cfg, params = setup
        prompt = [5, 9, 2, 7]
        g = Generator(params, cfg, batch=1)
        generated = g.generate(prompt, max_new_tokens=6)

        seq = list(prompt)
        for expect in generated:
            logits = transformer.forward(params, jnp.asarray([seq], jnp.int32), cfg)
            nxt = int(jnp.argmax(logits[0, -1, : cfg.vocab_size]))
            assert nxt == expect, (seq, nxt, expect)
            seq.append(nxt)

    def test_streaming_and_sampling(self, setup):
        cfg, params = setup
        g = Generator(params, cfg, batch=1)
        stream = g.generate([1], max_new_tokens=5, stream=True)
        tokens = [next(stream) for _ in range(3)]
        assert len(tokens) == 3
        sampled = g.generate([1], max_new_tokens=5, temperature=1.0, seed=7)
        assert len(sampled) == 5

    def test_rope_model_decode_parity(self):
        cfg = transformer.tiny(max_seq_len=32, pos="rope", tie_embeddings=False)
        params = transformer.init_params(cfg, jax.random.key(3))
        seq = np.random.default_rng(2).integers(0, cfg.vocab_size, 10).astype(np.int32)
        cache = init_cache(cfg, 1)
        logits, cache = _forward_cached(params, jnp.asarray(seq[None, :6]), cache, cfg, 0)
        for i in range(6, 10):
            logits, cache = _forward_cached(
                params, jnp.asarray(seq[None, i : i + 1]), cache, cfg, i
            )
        full = transformer.forward(params, jnp.asarray(seq[None, :]), cfg)
        np.testing.assert_allclose(
            np.asarray(full[0, -1], np.float32),
            np.asarray(logits[0, -1], np.float32),
            rtol=3e-4, atol=3e-4,
        )


class TestChunkedDecode:
    """serve/llm.py fast path: bucketed prefill + lax.scan decode chunks
    over the paged pool, against the per-token ``Generator``."""

    @pytest.fixture()
    def setup(self):
        cfg = transformer.tiny(max_seq_len=64)
        params = transformer.init_params(cfg, jax.random.key(0))
        return cfg, params

    def test_chunked_matches_per_token_greedy(self, setup):
        cfg, params = setup
        from ray_tpu.serve.llm import LLMEngine

        prompt = [3, 1, 4, 1, 5]
        g = Generator(params, cfg, batch=1)
        oracle = g.generate(prompt, max_new_tokens=12)
        eng = LLMEngine(params, cfg, chunk=4)
        got = eng.generate(prompt, max_new_tokens=12)
        assert got == oracle
        assert eng.kv.active_blocks() == 0

    def test_bucket_padding_is_invisible(self, setup):
        """Prompt of 5 pads to bucket 16; tokens must match the unpadded
        per-token oracle (pad K/V never attendable)."""
        cfg, params = setup
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(params, cfg, chunk=4, prompt_buckets=(16, 64))
        prompt = [7, 2, 9]
        got = eng.generate(prompt, max_new_tokens=8)
        oracle = Generator(params, cfg, batch=1).generate(prompt, max_new_tokens=8)
        assert got == oracle
        assert eng.kv.active_blocks() == 0

    def test_sampled_stream_runs(self, setup):
        cfg, params = setup
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(params, cfg, chunk=4)
        toks = eng.generate([1, 2], max_new_tokens=6, temperature=0.8, seed=3)
        assert len(toks) == 6
        assert all(0 <= t < cfg.vocab_size for t in toks)
        assert eng.kv.active_blocks() == 0

    def test_prompt_too_long_raises(self, setup):
        cfg, params = setup
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(params, cfg, chunk=8)  # max_len 64
        with pytest.raises(ValueError, match="no room"):
            eng.generate(list(range(1, 60)), max_new_tokens=4)
        assert eng.kv.active_blocks() == 0

    def test_length_cap_finish_reason(self, setup):
        cfg, params = setup
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(params, cfg, chunk=8)  # max_len 64
        # 16-token prompt leaves 48 slots = 6 chunks; ask for more.
        toks = eng.generate([1] * 16, max_new_tokens=100)
        assert len(toks) == 48
        assert eng.finish_reason == "length_cap"
        assert eng.kv.active_blocks() == 0
