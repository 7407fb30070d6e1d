"""LLM post-training RL — the RLAX-style actor-learner split on TPU parts.

The workload that ties the serving and training stacks together
(PAPERS.md RLAX): **generation actors** sample completions for a prompt
dataset from the paged continuous-batching engine
(``serve/llm.py LLMEngine`` over ``models/generate.py`` — repeated
prompts hit the prefix cache, so rollout prefill cost amortizes across
rounds), a **pluggable reward function** scores them into the replay
buffer (``rllib/replay.py``), and a **policy-gradient learner** updates a
toy transformer with the APPO loss shape — clipped surrogate over
per-token sequence log-probs, advantage = reward − batch baseline.
Weights flow back learner→generators every ``weight_sync_interval``
iterations (the staleness knob); each sync resets the generators' KV pool
since cached K/V computed under old params would otherwise leak into new
rollouts.

The whole loop is deterministic under a fixed seed: request seeds are a
counter over the base seed, prompts round-robin the dataset, and the
driver consumes generator results in fixed order — the reward-improvement
acceptance test relies on this.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
import optax

import ray_tpu
from ray_tpu.models import transformer
from ray_tpu.rllib.learner import Learner
from ray_tpu.rllib.replay import PrioritizedReplayBuffer
from ray_tpu.utils.logging import get_logger, log_swallowed

logger = get_logger(__name__)


def _default_reward(prompt: Sequence[int], completion: Sequence[int],
                    target: int = 3) -> float:
    """Toy dense reward: fraction of completion tokens equal to ``target``.
    Trivially gameable by design — the smoke test only needs a signal the
    policy gradient can climb deterministically."""
    if not len(completion):
        return 0.0
    return float(np.mean(np.asarray(completion) == target))


@dataclass
class LLMRLConfig:
    # Toy transformer shape (models/transformer.py tiny() overrides).
    # vocab_size stays a multiple of vocab_multiple so sampled ids < vocab.
    model_kwargs: Dict[str, Any] = field(default_factory=dict)
    # Prompt dataset: token-id lists. None = a small synthetic set.
    prompts: Optional[List[List[int]]] = None
    # reward_fn(prompt_tokens, completion_tokens) -> float
    reward_fn: Callable[[Sequence[int], Sequence[int]], float] = _default_reward
    num_generators: int = 2
    rollouts_per_iter: int = 16       # completions sampled per iteration
    max_new_tokens: int = 8
    temperature: float = 1.0
    train_batch: int = 32             # sequences per learner update
    updates_per_iter: int = 8
    buffer_capacity: int = 1024
    lr: float = 1e-2
    clip_param: float = 0.3
    grad_clip: float = 1.0
    # Iterations between learner→generator weight broadcasts (staleness).
    weight_sync_interval: int = 1
    engine_slots: int = 2
    seed: int = 0

    def build(self) -> "LLMRL":
        return LLMRL(self)


class GenerationActor:
    """Samples completions from a private paged LLM engine and returns the
    padded columnar rollout (tokens / mask / behavior log-probs)."""

    def __init__(self, model_config, *, slots: int = 2, seed: int = 0):
        from ray_tpu.serve.llm import LLMEngine

        self.model_config = model_config
        self._seed = seed
        # The stored tree is the actor's: the engine reads a working copy.
        self._params = transformer.init_params(model_config,
                                               jax.random.key(seed))
        self._engine = LLMEngine(
            self._params, model_config, slots=slots,
            max_len=model_config.max_seq_len, chunk=4, name="llm-rl-gen")
        self._max_len = int(model_config.max_seq_len)
        # Behavior log-probs under the params that SAMPLED the tokens (the
        # importance-ratio denominator): one extra forward over the padded
        # sequence, jitted once for the fixed max_len shape.
        self._logp_fn = jax.jit(self._token_logps)

    def _token_logps(self, params, tokens):
        # tokens [1, L] → per-position log p(tokens[t] | tokens[<t]), [1, L]
        # (position 0 is a dummy; masks never select it).
        logits = transformer.forward(params, tokens, self.model_config)
        logp_all = jax.nn.log_softmax(logits[:, :-1, :], axis=-1)
        tgt = tokens[:, 1:]
        logp = jnp.take_along_axis(logp_all, tgt[..., None], axis=-1)[..., 0]
        return jnp.concatenate([jnp.zeros_like(logp[:, :1]), logp], axis=1)

    def set_weights(self, params) -> bool:
        # No requests are in flight between rollout() calls, so the
        # engine's reset (its cached K/V is the OLD params') is safe.
        self._params = jax.tree.map(jnp.asarray, params)
        self._engine.set_params(self._params)
        return True

    def ping(self) -> bool:
        return True

    def rollout(self, prompts: List[List[int]], seeds: List[int],
                max_new_tokens: int, temperature: float) -> Dict[str, np.ndarray]:
        """Generate one completion per (prompt, seed); returns fixed-width
        columns padded to the engine max_len."""
        B, L = len(prompts), self._max_len
        tokens = np.zeros((B, L), np.int32)
        gen_mask = np.zeros((B, L), np.float32)
        behavior_logp = np.zeros((B, L), np.float32)
        prompt_len = np.zeros(B, np.int32)
        gen_len = np.zeros(B, np.int32)
        for b, (prompt, seed) in enumerate(zip(prompts, seeds)):
            completion = self._engine.generate(
                prompt, max_new_tokens=max_new_tokens,
                temperature=temperature, seed=int(seed))
            seq = list(prompt) + list(completion)
            n, p = len(seq), len(prompt)
            tokens[b, :n] = seq
            gen_mask[b, p:n] = 1.0
            prompt_len[b] = p
            gen_len[b] = n - p
            logp = np.asarray(self._logp_fn(
                self._params, tokens[b][None]))[0]
            behavior_logp[b] = logp * gen_mask[b]
        return {
            "tokens": tokens,
            "gen_mask": gen_mask,
            "behavior_logp": behavior_logp,
            "prompt_len": prompt_len,
            "gen_len": gen_len,
        }

    def kv_stats(self) -> Dict[str, float]:
        return self._engine.stats()

    def stop(self) -> None:
        close = getattr(self._engine, "close", None)
        if close is not None:
            close()


class LLMRLLearner(Learner):
    """Clipped-surrogate policy gradient over sequence log-probs — the
    APPO loss shape (appo.py ``_pg_loss``) applied per completion token,
    riding the base Learner's jitted optimizer machinery."""

    def __init__(self, model_config, config: Dict[str, Any], seed: int = 0):
        self.spec = None
        self.model_config = model_config
        self.config = dict(config)
        self.device = jax.local_devices(backend="cpu")[0]
        self.params = jax.device_put(
            transformer.init_params(model_config, jax.random.key(seed)),
            self.device)
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(self.config.get("grad_clip", 1.0)),
            optax.adam(self.config.get("lr", 3e-3)),
        )
        self.opt_state = jax.device_put(self.optimizer.init(self.params),
                                        self.device)
        self._update_fn = jax.jit(self._update)

    def loss_fn(self, params, batch) -> jax.Array:
        clip = self.config.get("clip_param", 0.3)
        tokens = batch["tokens"].astype(jnp.int32)
        logits = transformer.forward(params, tokens, self.model_config)
        logp_all = jax.nn.log_softmax(logits[:, :-1, :], axis=-1)
        tgt = tokens[:, 1:]
        logp = jnp.take_along_axis(logp_all, tgt[..., None], axis=-1)[..., 0]
        mask = batch["gen_mask"][:, 1:]
        behavior = batch["behavior_logp"][:, 1:]
        adv = batch["advantage"][:, None]          # [B, 1] per-sequence
        ratio = jnp.exp(logp - behavior)
        surrogate = jnp.minimum(
            ratio * adv,
            jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * adv)
        denom = jnp.maximum(mask.sum(), 1.0)
        return -jnp.sum(surrogate * mask) / denom


class LLMRL:
    """The end-to-end post-training loop (Tune-compatible ``train()``)."""

    def __init__(self, config: LLMRLConfig):
        self.config = config
        kw = dict(config.model_kwargs)
        self.model_config = transformer.tiny(**kw)
        assert (self.model_config.padded_vocab
                == self.model_config.vocab_size), \
            "vocab must pad to itself or sampled ids could exceed vocab"
        self.learner = LLMRLLearner(
            self.model_config,
            {"lr": config.lr, "clip_param": config.clip_param,
             "grad_clip": config.grad_clip},
            seed=config.seed)
        gen_cls = ray_tpu.remote(GenerationActor)
        self._generators = [
            gen_cls.remote(self.model_config, slots=config.engine_slots,
                           seed=config.seed)
            for _ in range(max(1, config.num_generators))
        ]
        self.prompts = config.prompts or self._default_prompts()
        self.buffer = PrioritizedReplayBuffer(
            config.buffer_capacity, alpha=0.0, seed=config.seed)
        self._iteration = 0
        self._rollouts = 0
        self._updates = 0
        # Generators start from the same seed as the learner, so their
        # params are already in sync; the first broadcast happens after the
        # first weight_sync_interval.

    def _default_prompts(self) -> List[List[int]]:
        rng = np.random.default_rng(self.config.seed + 7)
        V = self.model_config.vocab_size
        return [list(rng.integers(1, V, size=4)) for _ in range(8)]

    def _next_prompt_batches(self) -> List[List[List[int]]]:
        """Deterministic round-robin split of this iteration's prompts
        across generators."""
        cfg = self.config
        batches: List[List[List[int]]] = [[] for _ in self._generators]
        for j in range(cfg.rollouts_per_iter):
            idx = (self._rollouts + j) % len(self.prompts)
            batches[j % len(self._generators)].append(self.prompts[idx])
        return batches

    def train(self) -> Dict[str, Any]:
        cfg = self.config
        # Staleness sync at iteration start: generators run the whole
        # iteration under these weights.
        if self._iteration > 0 and cfg.weight_sync_interval > 0 \
                and self._iteration % cfg.weight_sync_interval == 0:
            weights = self.learner.get_weights()
            ray_tpu.get([g.set_weights.remote(weights)
                         for g in self._generators])

        batches = self._next_prompt_batches()
        seed0 = cfg.seed + 100_000
        refs = []
        offset = 0
        for g, prompt_batch in zip(self._generators, batches):
            if not prompt_batch:
                continue
            seeds = [seed0 + self._rollouts + offset + j
                     for j in range(len(prompt_batch))]
            offset += len(prompt_batch)
            refs.append((g, prompt_batch,
                         g.rollout.remote(prompt_batch, seeds,
                                          cfg.max_new_tokens,
                                          cfg.temperature)))
        self._rollouts += cfg.rollouts_per_iter

        rewards: List[float] = []
        # Fixed consumption order keeps the run deterministic even though
        # the generators sample concurrently.
        for g, prompt_batch, ref in refs:
            out = ray_tpu.get(ref)
            B = len(prompt_batch)
            batch_rewards = np.zeros(B, np.float32)
            for b in range(B):
                p, n = int(out["prompt_len"][b]), int(out["gen_len"][b])
                completion = out["tokens"][b, p:p + n].tolist()
                batch_rewards[b] = cfg.reward_fn(prompt_batch[b], completion)
            rewards.extend(batch_rewards.tolist())
            self.buffer.add_batch({
                "tokens": out["tokens"],
                "gen_mask": out["gen_mask"],
                "behavior_logp": out["behavior_logp"],
                "reward": batch_rewards,
            })

        losses = []
        for _ in range(cfg.updates_per_iter):
            if len(self.buffer) < cfg.train_batch:
                break
            sampled = self.buffer.sample(cfg.train_batch)
            batch = {
                "tokens": sampled["tokens"],
                "gen_mask": sampled["gen_mask"],
                "behavior_logp": sampled["behavior_logp"],
                # Advantage = reward − batch baseline (the RLAX-style
                # leave-nothing-to-a-critic estimator for bandit-style
                # sequence rewards).
                "advantage": (sampled["reward"]
                              - float(np.mean(sampled["reward"]))),
            }
            losses.append(self.learner.update(batch)["loss"])
            self._updates += 1

        self._iteration += 1
        return {
            "training_iteration": self._iteration,
            "reward_mean": float(np.mean(rewards)) if rewards else float("nan"),
            "loss": float(np.mean(losses)) if losses else float("nan"),
            "num_updates": self._updates,
            "num_rollouts": self._rollouts,
            "buffer_size": len(self.buffer),
        }

    def stop(self) -> None:
        for g in self._generators:
            try:
                ray_tpu.get(g.stop.remote(), timeout=10.0)
            except Exception:  # noqa: BLE001
                log_swallowed(logger, "generation actor stop")
            try:
                ray_tpu.kill(g)
            except Exception:  # noqa: BLE001
                log_swallowed(logger, "generation actor kill")
