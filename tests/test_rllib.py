"""RLlib tests, modeled on the reference's ``rllib/tests`` + per-algorithm
tests: module forward/dist math, GAE correctness, env-runner sampling,
learner descent, distributed learner parity, and the PPO CartPole learning
gate (the reference's tuned-example regression style: "reaches reward R").
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu.rllib import (
    PPO,
    PPOConfig,
    PPOLearner,
    RLModule,
    RLModuleSpec,
    SingleAgentEnvRunner,
    compute_gae,
)


def cartpole():
    import gymnasium as gym

    return gym.make("CartPole-v1")


class TestRLModule:
    def test_forward_shapes_discrete(self):
        spec = RLModuleSpec(observation_dim=4, action_dim=2)
        m = RLModule(spec)
        params = m.init_params(jax.random.key(0))
        out = m.forward_train(params, jnp.zeros((7, 4)))
        assert out["action_dist_inputs"].shape == (7, 2)
        assert out["vf_preds"].shape == (7,)

    def test_sample_and_logp_consistent(self):
        spec = RLModuleSpec(observation_dim=4, action_dim=3)
        m = RLModule(spec)
        params = m.init_params(jax.random.key(0))
        obs = jax.random.normal(jax.random.key(1), (64, 4))
        a, logp, v = m.sample_action(params, obs, jax.random.key(2))
        logp2, ent, v2 = m.logp_and_entropy(params, obs, a)
        np.testing.assert_allclose(np.asarray(logp), np.asarray(logp2), rtol=1e-5)
        assert np.all(np.asarray(ent) > 0)

    def test_continuous_action_space(self):
        spec = RLModuleSpec(observation_dim=3, action_dim=2, discrete=False)
        m = RLModule(spec)
        params = m.init_params(jax.random.key(0))
        obs = jnp.zeros((5, 3))
        a, logp, v = m.sample_action(params, obs, jax.random.key(1))
        assert a.shape == (5, 2)
        logp2, ent, _ = m.logp_and_entropy(params, obs, a)
        np.testing.assert_allclose(np.asarray(logp), np.asarray(logp2), rtol=1e-4)


class TestGAE:
    def test_matches_manual_single_env(self):
        rewards = np.array([[1.0], [1.0], [1.0]], np.float32)
        values = np.array([[0.5], [0.5], [0.5]], np.float32)
        terms = np.zeros((3, 1), np.float32)
        boot = np.array([0.5], np.float32)
        adv, tgt = compute_gae(rewards, values, terms, boot, gamma=0.9, lambda_=1.0)
        # manual: delta_t = 1 + 0.9*V(t+1) - 0.5
        d2 = 1 + 0.9 * 0.5 - 0.5
        d1 = d2
        d0 = d2
        expected2 = d2
        expected1 = d1 + 0.9 * expected2
        expected0 = d0 + 0.9 * expected1
        np.testing.assert_allclose(adv[:, 0], [expected0, expected1, expected2], rtol=1e-5)
        np.testing.assert_allclose(tgt, adv + values)

    def test_termination_stops_bootstrap(self):
        rewards = np.ones((2, 1), np.float32)
        values = np.zeros((2, 1), np.float32)
        terms = np.array([[1.0], [0.0]], np.float32)
        boot = np.array([100.0], np.float32)
        adv, _ = compute_gae(rewards, values, terms, boot, gamma=0.9, lambda_=0.95)
        # t=0 terminated: no bootstrap from t=1 values
        assert adv[0, 0] == pytest.approx(1.0)



    def test_autoreset_step_cut_and_bootstrap(self):
        """gymnasium NEXT_STEP autoreset: the step after a done is a junk
        transition (action ignored, reward 0, obs = final obs of the old
        episode). valids must (a) zero its advantage, (b) cut the GAE trace
        so the new episode's deltas don't leak backward, and (c) leave
        V(final obs) as the truncation bootstrap for the preceding step."""
        gamma, lam = 0.9, 0.95
        # t=0: last real step of ep A (truncated); t=1: junk autoreset step
        # whose value is V(final obs of A); t=2: first real step of ep B.
        rewards = np.array([[1.0], [0.0], [2.0]], np.float32)
        values = np.array([[0.5], [0.7], [0.3]], np.float32)
        terms = np.zeros((3, 1), np.float32)
        valids = np.array([[1.0], [0.0], [1.0]], np.float32)
        boot = np.array([0.4], np.float32)
        adv, tgt = compute_gae(
            rewards, values, terms, boot, gamma=gamma, lambda_=lam, valids=valids
        )
        # t=2 (new episode): plain one-step + bootstrap
        d2 = 2.0 + gamma * 0.4 - 0.3
        assert adv[2, 0] == pytest.approx(d2, rel=1e-5)
        # t=1 (junk): advantage zeroed
        assert adv[1, 0] == 0.0
        # t=0 (truncated): bootstraps with V(final obs)=values[1], and the
        # trace does NOT include d2 (no cross-episode leak)
        d0 = 1.0 + gamma * 0.7 - 0.5
        assert adv[0, 0] == pytest.approx(d0, rel=1e-5)

    def test_no_valids_matches_legacy(self):
        rewards = np.ones((4, 2), np.float32)
        values = np.full((4, 2), 0.3, np.float32)
        terms = np.zeros((4, 2), np.float32)
        boot = np.full(2, 0.3, np.float32)
        a1, t1 = compute_gae(rewards, values, terms, boot, gamma=0.9, lambda_=0.9)
        a2, t2 = compute_gae(
            rewards, values, terms, boot, gamma=0.9, lambda_=0.9,
            valids=np.ones((4, 2), np.float32),
        )
        np.testing.assert_allclose(a1, a2)
        np.testing.assert_allclose(t1, t2)


class TestEnvRunner:

    def test_valids_mark_autoreset_steps(self):
        """The step AFTER each done must be flagged invalid (gymnasium
        NEXT_STEP autoreset: that step's action is ignored by the env)."""
        import gymnasium as gym

        def short_ep():
            return gym.make("CartPole-v1", max_episode_steps=4)

        r = SingleAgentEnvRunner(short_ep, num_envs=2, seed=0)
        batch = r.sample(12)
        valids = batch["valids"]
        rewards = batch["rewards"]
        assert valids.shape == (12, 2)
        # every invalid step has reward 0 (env ignored the action)
        assert np.all(rewards[valids == 0.0] == 0.0)
        # episodes cap at 4 steps -> dones occur -> some autoreset steps
        assert (valids == 0.0).sum() >= 2
        # an invalid step is always immediately preceded by a done step:
        # valid transitions and nonzero reward at t-1
        T, N = valids.shape
        for t in range(1, T):
            for n in range(N):
                if valids[t, n] == 0.0:
                    assert valids[t - 1, n] == 1.0  # never two junk in a row
        r.stop()

    def test_sample_shapes_and_metrics(self):
        r = SingleAgentEnvRunner(cartpole, num_envs=3, seed=0)
        batch = r.sample(20)
        assert batch["obs"].shape == (20, 3, 4)
        assert batch["actions"].shape == (20, 3)
        assert batch["bootstrap_value"].shape == (3,)
        r.sample(200)  # enough for some episodes to finish
        m = r.get_metrics()
        assert m["num_episodes"] > 0
        assert 5 < m["episode_return_mean"] < 100  # random policy range
        r.stop()


class TestLearner:
    def _fake_batch(self, n=128, obs_dim=4, n_act=2, seed=0):
        rng = np.random.default_rng(seed)
        return {
            "obs": rng.normal(size=(n, obs_dim)).astype(np.float32),
            "actions": rng.integers(0, n_act, n).astype(np.float32),
            "logp": np.full(n, -0.69, np.float32),
            "advantages": rng.normal(size=n).astype(np.float32),
            "value_targets": rng.normal(size=n).astype(np.float32),
        }

    def test_update_decreases_loss(self):
        spec = RLModuleSpec(observation_dim=4, action_dim=2)
        cfg = {"lr": 1e-2, "clip_param": 0.2, "vf_clip_param": 10.0,
               "vf_loss_coeff": 0.5, "entropy_coeff": 0.0, "grad_clip": 10.0}
        learner = PPOLearner(spec, cfg)
        batch = self._fake_batch()
        losses = [learner.update(batch)["loss"] for _ in range(20)]
        assert losses[-1] < losses[0]

    def test_learner_group_parity_local_vs_distributed(self, ray_start_regular):
        """2 distributed learners with gradient allreduce must track the
        local learner bit-for-bit on the same total batch."""
        from ray_tpu.rllib.learner import LearnerGroup

        spec = RLModuleSpec(observation_dim=4, action_dim=2)
        cfg = {"lr": 1e-2, "clip_param": 0.2, "vf_clip_param": 10.0,
               "vf_loss_coeff": 0.5, "entropy_coeff": 0.0, "grad_clip": 10.0}
        local = LearnerGroup(PPOLearner, spec, cfg, num_learners=0, seed=3)
        dist = LearnerGroup(PPOLearner, spec, cfg, num_learners=2,
                            group_name="test_lg", seed=3)
        batch = self._fake_batch(n=64, seed=5)
        for _ in range(3):
            local.update(batch)
            dist.update(batch)
        w_local = local.get_weights()
        w_dist = dist.get_weights()
        for a, b in zip(jax.tree.leaves(w_local), jax.tree.leaves(w_dist)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        dist.shutdown()


class TestPPOE2E:
    @pytest.mark.slow  # a learning curve (PR 50): `-m slow` runs it
    def test_cartpole_learns(self):
        """The learning-regression gate (reference:
        ``rllib/tuned_examples/ppo/cartpole-ppo.yaml`` — reach return R)."""
        algo = PPOConfig().environment(cartpole).env_runners(
            num_envs_per_env_runner=8
        ).training(
            rollout_fragment_length=128,
            num_epochs=6,
            minibatch_size=256,
            lr=3e-4,
            entropy_coeff=0.01,
            seed=1,
        ).build()
        best = 0.0
        for i in range(30):
            result = algo.train()
            r = result["episode_return_mean"]
            if not np.isnan(r):
                best = max(best, r)
            if best >= 120.0:
                break
        algo.stop()
        assert best >= 120.0, f"PPO failed to learn CartPole: best={best}"

    def test_remote_env_runners_and_checkpoint(self, ray_start_regular, tmp_path):
        algo = PPOConfig().environment(cartpole).env_runners(
            num_env_runners=2, num_envs_per_env_runner=2
        ).training(rollout_fragment_length=32, num_epochs=2,
                   minibatch_size=64, seed=0).build()
        r1 = algo.train()
        assert r1["timesteps_total"] == 2 * 2 * 32
        path = str(tmp_path / "ck")
        algo.save(path)
        w_before = algo.learner_group.get_weights()
        algo.train()
        algo.restore(path)
        w_after = algo.learner_group.get_weights()
        for a, b in zip(jax.tree.leaves(w_before), jax.tree.leaves(w_after)):
            np.testing.assert_array_equal(a, b)
        algo.stop()


class TestVtrace:
    def test_vtrace_matches_naive_reference(self):
        """Scan-based V-trace vs a direct O(T^2) transcription of Espeholt
        et al. eq. 1."""
        import numpy as np
        from ray_tpu.rllib.impala import vtrace

        rng = np.random.default_rng(0)
        T, N = 7, 3
        gamma, rho_bar, c_bar = 0.95, 1.0, 1.0
        b_logp = rng.normal(0, 0.3, (T, N)).astype(np.float32)
        t_logp = rng.normal(0, 0.3, (T, N)).astype(np.float32)
        rewards = rng.normal(0, 1, (T, N)).astype(np.float32)
        values = rng.normal(0, 1, (T, N)).astype(np.float32)
        bootstrap = rng.normal(0, 1, N).astype(np.float32)
        dones = (rng.random((T, N)) < 0.2).astype(np.float32)

        vs, pg_adv = vtrace(b_logp, t_logp, rewards, values, bootstrap,
                            dones, gamma=gamma, rho_bar=rho_bar, c_bar=c_bar)

        # naive: vs_t = V_t + sum_{k>=t} (prod_{i=t..k-1} disc_i c_i) delta_k
        rho = np.minimum(rho_bar, np.exp(t_logp - b_logp))
        c = np.minimum(c_bar, np.exp(t_logp - b_logp))
        disc = gamma * (1 - dones)
        nv = np.concatenate([values[1:], bootstrap[None]], axis=0)
        deltas = rho * (rewards + disc * nv - values)
        vs_naive = values.copy()
        for t in range(T):
            for k in range(t, T):
                coef = np.ones(N, np.float32)
                for i in range(t, k):
                    coef *= disc[i] * c[i]
                vs_naive[t] += coef * deltas[k]
        np.testing.assert_allclose(np.asarray(vs), vs_naive, rtol=1e-4, atol=1e-4)
        vs_next = np.concatenate([np.asarray(vs)[1:], bootstrap[None]], axis=0)
        adv_naive = rho * (rewards + disc * vs_next - values)
        np.testing.assert_allclose(np.asarray(pg_adv), adv_naive, rtol=1e-4, atol=1e-4)

    def test_vtrace_on_policy_reduces_to_discounted_returns(self):
        """With pi == mu and lambda-free targets, vs equals the n-step
        discounted return (no clipping active)."""
        import numpy as np
        from ray_tpu.rllib.impala import vtrace

        T, N = 5, 2
        logp = np.zeros((T, N), np.float32)
        rewards = np.ones((T, N), np.float32)
        values = np.zeros((T, N), np.float32)
        bootstrap = np.zeros(N, np.float32)
        dones = np.zeros((T, N), np.float32)
        vs, _ = vtrace(logp, logp, rewards, values, bootstrap, dones,
                       gamma=0.9)
        expect = np.array([sum(0.9 ** (k - t) for k in range(t, T))
                           for t in range(T)], np.float32)
        np.testing.assert_allclose(np.asarray(vs)[:, 0], expect, rtol=1e-5)


class TestConvModule:
    def test_conv_forward_shapes_and_grad(self):
        import numpy as np
        import jax
        from ray_tpu.rllib.rl_module import RLModule, RLModuleSpec

        spec = RLModuleSpec(observation_dim=84 * 84 * 4, action_dim=6,
                            discrete=True, conv=True, obs_shape=(84, 84, 4),
                            hidden=(512,))
        mod = RLModule(spec)
        params = mod.init_params(jax.random.key(0))
        obs = np.random.default_rng(0).integers(
            0, 255, (3, 84 * 84 * 4)).astype(np.float32)
        out = mod.forward_train(params, obs)
        assert out["action_dist_inputs"].shape == (3, 6)
        assert out["vf_preds"].shape == (3,)
        logp, ent, v = mod.logp_and_entropy(params, obs, np.array([0, 2, 5]))
        assert logp.shape == (3,)

    def test_spec_for_env_detects_pixels(self):
        from ray_tpu.rllib.envs import SyntheticAtariEnv
        from ray_tpu.rllib.rl_module import spec_for_env

        env = SyntheticAtariEnv()
        spec = spec_for_env(env)
        assert spec.conv and spec.obs_shape == (84, 84, 4)
        assert spec.action_dim == 6


class TestImpala:
    def test_impala_learns_cartpole(self, ray_start_regular):
        """Async IMPALA improves CartPole return (learning smoke gate)."""
        import gymnasium as gym
        import numpy as np
        from ray_tpu.rllib.impala import ImpalaConfig

        algo = (
            ImpalaConfig()
            .environment(lambda: gym.make("CartPole-v1"))
            .env_runners(num_env_runners=2, num_envs_per_env_runner=4)
            .training(rollout_fragment_length=64, lr=5e-3,
                      broadcast_interval=1)
            .build()
        )
        try:
            first = None
            best = -np.inf
            for i in range(12):
                result = algo.train()
                r = result["episode_return_mean"]
                if not np.isnan(r):
                    first = r if first is None else first
                    best = max(best, r)
            assert first is not None, "no episodes completed"
            assert best > max(first * 1.3, 40.0), (first, best)
        finally:
            algo.stop()

    def test_impala_with_aggregators(self, ray_start_regular):
        import gymnasium as gym
        from ray_tpu.rllib.impala import ImpalaConfig

        algo = (
            ImpalaConfig()
            .environment(lambda: gym.make("CartPole-v1"))
            .env_runners(num_env_runners=2, num_envs_per_env_runner=2)
            .training(rollout_fragment_length=32, num_aggregators=1,
                      train_batch_fragments=2)
            .build()
        )
        try:
            result = algo.train()
            assert result["num_updates"] >= 1
            assert result["timesteps_total"] > 0
        finally:
            algo.stop()


class TestSyntheticAtariPPO:
    def test_ppo_runs_on_pixels(self, ray_start_regular):
        """Conv PPO end-to-end on the Atari stand-in (throughput > 0)."""
        from ray_tpu.rllib.envs import SyntheticAtariEnv
        from ray_tpu.rllib.ppo import PPOConfig

        algo = (
            PPOConfig()
            .environment(lambda: SyntheticAtariEnv(max_steps=200))
            .env_runners(num_env_runners=0, num_envs_per_env_runner=2)
            .training(rollout_fragment_length=16, num_epochs=1,
                      minibatch_size=16, hidden=())
            .build()
        )
        try:
            result = algo.train()
            assert result["env_steps_per_sec"] > 0
            assert np.isfinite(result["loss"])
        finally:
            algo.stop()


class TestDQN:
    """DQN family (reference: rllib/algorithms/dqn/dqn.py)."""

    def test_replay_buffer_ring_and_sample(self):
        from ray_tpu.rllib import ReplayBuffer

        buf = ReplayBuffer(capacity=8, seed=0)
        buf.add_batch({"x": np.arange(6, dtype=np.float32)})
        assert len(buf) == 6
        buf.add_batch({"x": np.arange(10, 14, dtype=np.float32)})
        assert len(buf) == 8  # wrapped
        s = buf.sample(16)
        assert s["x"].shape == (16,)
        # wrapped slots hold the newest values
        assert set(np.unique(s["x"])) <= {2, 3, 4, 5, 10, 11, 12, 13}

    def test_td_targets_and_target_sync(self):
        """Double-DQN targets use the target net for evaluation; the target
        net only moves on the sync boundary."""
        from ray_tpu.rllib.dqn import DQNLearner
        from ray_tpu.rllib.rl_module import RLModuleSpec

        spec = RLModuleSpec(observation_dim=4, action_dim=2, hidden=(16,))
        lrn = DQNLearner(spec, {"lr": 1e-2, "gamma": 0.9,
                                "target_update_freq": 3}, seed=0)
        before = jax.tree.leaves(lrn.target_params)[0].copy()
        batch = {
            "obs": np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32),
            "actions": np.zeros(32, np.int64),
            "rewards": np.ones(32, np.float32),
            "next_obs": np.random.default_rng(1).normal(size=(32, 4)).astype(np.float32),
            "terminateds": np.zeros(32, np.float32),
        }
        lrn.update(batch)
        lrn.update(batch)
        after2 = jax.tree.leaves(lrn.target_params)[0]
        np.testing.assert_array_equal(before, after2)  # not synced yet
        lrn.update(batch)  # 3rd update -> sync
        after3 = jax.tree.leaves(lrn.target_params)[0]
        assert not np.array_equal(before, after3)
        # terminal transitions: target == reward exactly
        t = lrn._targets_fn(lrn.target_params, lrn.params,
                            jnp.asarray(batch["next_obs"]),
                            jnp.asarray(batch["rewards"]),
                            jnp.ones(32),
                            jnp.full(32, 0.9))  # per-sample γ^s column
        np.testing.assert_allclose(np.asarray(t), batch["rewards"], rtol=1e-6)

    def test_dqn_learns_cartpole(self, ray_start_regular):
        """The learning-regression gate (reference:
        rllib/tuned_examples/dqn/cartpole-dqn.yaml — improve return)."""
        import gymnasium as gym

        from ray_tpu.rllib import DQNConfig

        algo = (
            DQNConfig()
            .environment(lambda: gym.make("CartPole-v1"))
            .env_runners(num_env_runners=1, num_envs_per_env_runner=8)
            .training(
                rollout_fragment_length=64,
                train_batch_size=64,
                updates_per_iteration=48,
                num_steps_sampled_before_learning=512,
                target_update_freq=60,
                epsilon_decay_timesteps=8_000,
                lr=1e-3,
                seed=3,
            )
            .build()
        )
        try:
            first, best = None, -np.inf
            for _ in range(25):
                result = algo.train()
                r = result["episode_return_mean"]
                if not np.isnan(r):
                    first = r if first is None else first
                    best = max(best, r)
                if best >= 100.0:
                    break
            assert first is not None, "no episodes completed"
            assert best >= max(first * 1.5, 60.0), (first, best)
        finally:
            algo.stop()

    def test_dqn_checkpoint_roundtrip(self, ray_start_regular, tmp_path):
        import gymnasium as gym

        from ray_tpu.rllib import DQNConfig

        algo = (DQNConfig()
                .environment(lambda: gym.make("CartPole-v1"))
                .training(num_steps_sampled_before_learning=64,
                          rollout_fragment_length=16, seed=0)
                .build())
        try:
            algo.train()
            path = algo.save(str(tmp_path / "ck"))
            w = algo.learner.get_weights()
            algo2 = (DQNConfig()
                     .environment(lambda: gym.make("CartPole-v1"))
                     .training(num_steps_sampled_before_learning=64,
                               rollout_fragment_length=16, seed=9)
                     .build())
            try:
                algo2.restore(path)
                w2 = algo2.learner.get_weights()
                for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(w2)):
                    np.testing.assert_array_equal(a, b)
            finally:
                algo2.stop()
        finally:
            algo.stop()


class TestImpalaLearnerGroup:
    def test_two_learner_impala_matches_single(self, ray_start_regular):
        """2 remote learners fed IDENTICAL batch halves must produce exactly
        the update a single learner gets from one half (the ring-allreduce
        mean of two equal gradients IS that gradient) — proving the group's
        gradient sync, not just 'it runs'."""
        from ray_tpu.rllib import ImpalaLearner, LearnerGroup
        from ray_tpu.rllib.rl_module import RLModuleSpec

        spec = RLModuleSpec(observation_dim=4, action_dim=2, hidden=(16,))
        cfg = {"lr": 1e-2, "gamma": 0.99, "vf_loss_coeff": 0.5,
               "entropy_coeff": 0.01, "grad_clip": 40.0}
        T, N = 8, 2
        rng = np.random.default_rng(0)
        half = {
            "obs": rng.normal(size=(T, N, 4)).astype(np.float32),
            "actions": rng.integers(0, 2, (T, N)).astype(np.float32),
            "logp": rng.normal(size=(T, N)).astype(np.float32) * 0.1 - 0.7,
            "rewards": rng.normal(size=(T, N)).astype(np.float32),
            "terminateds": np.zeros((T, N), np.float32),
            "valids": np.ones((T, N), np.float32),
            "bootstrap_obs": rng.normal(size=(N, 4)).astype(np.float32),
        }
        double = {k: (np.concatenate([v, v], axis=1) if v.ndim >= 2 and k != "bootstrap_obs"
                      else np.concatenate([v, v], axis=0))
                  for k, v in half.items()}

        single = ImpalaLearner(spec, cfg, seed=0)
        single.update(half)
        expected = single.get_weights()

        group = LearnerGroup(
            ImpalaLearner, spec, cfg, num_learners=2,
            group_name="impala-parity", seed=0,
            shard_axes={"obs": 1, "actions": 1, "logp": 1, "values": 1,
                        "rewards": 1, "terminateds": 1, "valids": 1,
                        "bootstrap_obs": 0},
        )
        try:
            group.update(double)
            got = group.get_weights()
            for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(got)):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        finally:
            group.shutdown()

    def test_impala_trains_with_learner_group(self, ray_start_regular):
        import gymnasium as gym

        from ray_tpu.rllib import ImpalaConfig

        algo = (ImpalaConfig()
                .environment(lambda: gym.make("CartPole-v1"))
                .env_runners(num_env_runners=2, num_envs_per_env_runner=2)
                .training(rollout_fragment_length=32, num_learners=2,
                          lr=5e-3)
                .build())
        try:
            for _ in range(3):
                result = algo.train()
            assert np.isfinite(result["loss"])
            assert result["timesteps_total"] > 0
        finally:
            algo.stop()


class TestPrioritizedReplay:
    def test_sum_tree_sampling_proportional(self):
        from ray_tpu.rllib.replay import _SumTree

        t = _SumTree(8)
        t.set(np.arange(4), np.array([1.0, 2.0, 3.0, 4.0]))
        assert abs(t.total - 10.0) < 1e-9
        rng = np.random.default_rng(0)
        idx = t.sample(rng.uniform(0, t.total, 20_000))
        counts = np.bincount(idx, minlength=8)[:4] / 20_000
        np.testing.assert_allclose(counts, [0.1, 0.2, 0.3, 0.4], atol=0.02)

    def test_per_prioritizes_high_error(self):
        from ray_tpu.rllib.replay import PrioritizedReplayBuffer

        buf = PrioritizedReplayBuffer(128, alpha=1.0, beta=0.4, seed=0)
        n = 64
        buf.add_batch({
            "obs": np.zeros((n, 4), np.float32),
            "rewards": np.arange(n, dtype=np.float32),
        })
        # Give transition 7 a huge TD error, everyone else tiny.
        errs = np.full(n, 0.01)
        errs[7] = 100.0
        buf.update_priorities(np.arange(n), errs)
        s = buf.sample(256)
        frac7 = float(np.mean(s["rewards"] == 7.0))
        assert frac7 > 0.5, frac7   # ~99% of the mass is on index 7
        assert s["weights"].min() > 0 and s["weights"].max() <= 1.0
        # The rare (low-priority) samples carry the LARGE correction weight.
        if (s["rewards"] != 7.0).any():
            assert (s["weights"][s["rewards"] != 7.0].min()
                    >= s["weights"][s["rewards"] == 7.0].max())

    def test_nstep_columns_chains_and_breaks(self):
        from ray_tpu.rllib.replay import nstep_columns

        # T=4, N=1: rewards 1,2,3,4; termination after step 1 (index 1).
        obs = np.arange(5, dtype=np.float32).reshape(5, 1, 1)[:4]
        rewards = np.array([[1.0], [2.0], [3.0], [4.0]], np.float32)
        terms = np.array([[0.0], [1.0], [0.0], [0.0]], np.float32)
        valids = np.ones((4, 1), np.float32)
        boot = np.array([[9.0]], np.float32)
        out = nstep_columns(obs, rewards, terms, valids, boot,
                            n_step=3, gamma=0.5)
        # t=0: chain crosses t=1 (terminal) -> R = 1 + 0.5*2, stops there.
        assert abs(out["rewards"][0] - 2.0) < 1e-6
        assert out["terminateds"][0] == 1.0
        assert abs(out["discounts"][0] - 0.25) < 1e-6  # gamma^2
        # t=2: full 2-chain to the fragment end: R = 3 + 0.5*4.
        assert abs(out["rewards"][2] - 5.0) < 1e-6
        assert out["next_obs"][2][0] == 9.0  # bootstrap obs
        # t=3: single step.
        assert abs(out["rewards"][3] - 4.0) < 1e-6

    def test_dqn_per_nstep_smoke(self, ray_start_regular):
        import gymnasium as gym

        from ray_tpu.rllib import DQNConfig

        algo = (DQNConfig()
                .environment(lambda: gym.make("CartPole-v1"))
                .training(num_steps_sampled_before_learning=64,
                          rollout_fragment_length=32,
                          updates_per_iteration=4,
                          replay="prioritized", n_step=3, seed=0)
                .build())
        try:
            r = algo.train()
            r = algo.train()
            assert np.isfinite(r["loss"])
            assert r["buffer_size"] > 0
        finally:
            algo.stop()


class TestSAC:
    def test_sac_module_squashing_and_logp(self):
        from ray_tpu.rllib.rl_module import RLModuleSpec
        from ray_tpu.rllib.sac import SACModule

        spec = RLModuleSpec(observation_dim=3, action_dim=1, discrete=False)
        m = SACModule(spec, np.array([-2.0], np.float32),
                      np.array([2.0], np.float32), hidden=(16,))
        params = m.init_params(jax.random.key(0))
        obs = jnp.zeros((32, 3))
        act, logp, unit = m.pi_sample(params["pi"], obs,
                                      jax.random.key(1))
        assert act.shape == (32, 1) and logp.shape == (32,)
        assert float(jnp.max(jnp.abs(act))) <= 2.0 + 1e-5
        q = m.q_value(params["q1"], obs, act)
        assert q.shape == (32,)

    @pytest.mark.slow  # a learning curve (PR 50): `-m slow` runs it
    def test_sac_learns_pendulum(self, ray_start_regular):
        """Continuous-control learning gate (reference:
        rllib/tuned_examples/sac/pendulum-sac.yaml — improve return)."""
        import gymnasium as gym

        from ray_tpu.rllib import SACConfig

        algo = (SACConfig()
                .environment(lambda: gym.make("Pendulum-v1"))
                .env_runners(num_env_runners=1, num_envs_per_env_runner=4)
                .training(
                    rollout_fragment_length=64,
                    train_batch_size=128,
                    updates_per_iteration=48,
                    num_steps_sampled_before_learning=512,
                    hidden=(64, 64),
                    lr=3e-3,
                    n_step=1,
                    seed=0,
                )
                .build())
        try:
            first, best = None, -np.inf
            for _ in range(30):
                result = algo.train()
                r = result["episode_return_mean"]
                if not np.isnan(r):
                    first = r if first is None else first
                    best = max(best, r)
                if best >= -300.0:
                    break
            assert first is not None, "no episodes completed"
            # Random policy sits near -1100 to -1400; learning must lift it.
            assert best >= first + 200.0 or best >= -400.0, (first, best)
        finally:
            algo.stop()

    def test_sac_checkpoint_roundtrip(self, ray_start_regular, tmp_path):
        import gymnasium as gym

        from ray_tpu.rllib import SACConfig

        algo = (SACConfig()
                .environment(lambda: gym.make("Pendulum-v1"))
                .training(num_steps_sampled_before_learning=32,
                          rollout_fragment_length=16,
                          updates_per_iteration=2,
                          train_batch_size=32, hidden=(16,), seed=0)
                .build())
        try:
            algo.train()
            path = algo.save(str(tmp_path / "sac_ck"))
            w = algo.learner.get_weights()
            algo2 = (SACConfig()
                     .environment(lambda: gym.make("Pendulum-v1"))
                     .training(num_steps_sampled_before_learning=32,
                               rollout_fragment_length=16,
                               updates_per_iteration=2,
                               train_batch_size=32, hidden=(16,), seed=5)
                     .build())
            try:
                algo2.restore(path)
                w2 = algo2.learner.get_weights()
                for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(w2)):
                    np.testing.assert_array_equal(a, b)
            finally:
                algo2.stop()
        finally:
            algo.stop()


class TestOffline:
    def _expert_dataset(self, n_episodes=40):
        """CartPole 'expert': a hand-written stabilizing controller
        (push toward upright), good for ~150-350 reward — enough signal
        for BC to beat random (~20)."""
        import gymnasium as gym

        env = gym.make("CartPole-v1")
        episodes = []
        for ep in range(n_episodes):
            obs, _ = env.reset(seed=ep)
            rows = {"obs": [], "actions": [], "rewards": []}
            done = False
            while not done:
                a = 1 if (obs[2] + 0.3 * obs[3]) > 0 else 0
                rows["obs"].append(np.asarray(obs, np.float32))
                rows["actions"].append(a)
                nobs, r, term, trunc, _ = env.step(a)
                rows["rewards"].append(float(r))
                obs = nobs
                done = term or trunc
            rows["terminated"] = term
            episodes.append(rows)
        env.close()
        return episodes

    @pytest.mark.slow  # a learning curve (PR 50): `-m slow` runs it
    def test_bc_clones_expert(self, ray_start_regular):
        import gymnasium as gym

        from ray_tpu.rllib import BCConfig, episodes_to_dataset

        ds = episodes_to_dataset(self._expert_dataset())
        algo = BCConfig(
            dataset=ds, observation_dim=4, action_dim=2, discrete=True,
            hidden=(32, 32), updates_per_iteration=64, lr=3e-3, seed=0,
        ).build()
        l0 = algo.train()["loss"]
        for _ in range(7):
            res = algo.train()
        assert res["loss"] < l0 * 0.6, (l0, res["loss"])
        ev = algo.evaluate(lambda: gym.make("CartPole-v1"), num_episodes=5)
        assert ev["episode_return_mean"] >= 100.0, ev

    @pytest.mark.slow  # a learning curve (PR 50): `-m slow` runs it
    def test_marwil_beats_bc_on_mixed_data(self, ray_start_regular):
        """Mixed-quality corpus: MARWIL's advantage weighting should favor
        the good trajectories; with beta=0 (BC) the clone averages the
        policies. At minimum MARWIL must stay trainable and its evaluation
        must not collapse vs BC."""
        import gymnasium as gym

        from ray_tpu.rllib import BCConfig, MARWILConfig, episodes_to_dataset

        # Half expert, half random actions.
        expert = self._expert_dataset(20)
        env = gym.make("CartPole-v1")
        rng = np.random.default_rng(0)
        bad = []
        for ep in range(20):
            obs, _ = env.reset(seed=1000 + ep)
            rows = {"obs": [], "actions": [], "rewards": []}
            done = False
            while not done:
                a = int(rng.integers(0, 2))
                rows["obs"].append(np.asarray(obs, np.float32))
                rows["actions"].append(a)
                nobs, r, term, trunc, _ = env.step(a)
                rows["rewards"].append(float(r))
                obs = nobs
                done = term or trunc
            rows["terminated"] = term
            bad.append(rows)
        env.close()
        ds = episodes_to_dataset(expert + bad)

        def fit(cfg_cls, **kw):
            algo = cfg_cls(
                dataset=ds, observation_dim=4, action_dim=2, discrete=True,
                hidden=(32, 32), updates_per_iteration=64, lr=3e-3, seed=0,
                **kw).build()
            for _ in range(8):
                algo.train()
            return algo.evaluate(lambda: gym.make("CartPole-v1"),
                                 num_episodes=5)["episode_return_mean"]

        marwil_ret = fit(MARWILConfig, beta=2.0)
        bc_ret = fit(BCConfig)
        assert marwil_ret >= 60.0, (marwil_ret, bc_ret)
        assert marwil_ret >= bc_ret * 0.8, (marwil_ret, bc_ret)

    def test_bc_checkpoint_roundtrip(self, ray_start_regular, tmp_path):
        from ray_tpu.rllib import BCConfig, episodes_to_dataset

        ds = episodes_to_dataset(self._expert_dataset(4))
        algo = BCConfig(dataset=ds, observation_dim=4, action_dim=2,
                        hidden=(16,), updates_per_iteration=4, seed=0).build()
        algo.train()
        path = algo.save(str(tmp_path / "bc_ck"))
        algo2 = BCConfig(dataset=ds, observation_dim=4, action_dim=2,
                         hidden=(16,), updates_per_iteration=4, seed=7).build()
        algo2.restore(path)
        for a, b in zip(jax.tree.leaves(algo.learner.get_weights()),
                        jax.tree.leaves(algo2.learner.get_weights())):
            np.testing.assert_array_equal(a, b)

    def test_per_non_power_of_two_capacity(self):
        """Regression: the sum tree must round up internally — default
        configs use capacities like 50_000."""
        from ray_tpu.rllib.replay import PrioritizedReplayBuffer

        buf = PrioritizedReplayBuffer(10, seed=0)
        buf.add_batch({"obs": np.arange(7, dtype=np.float32).reshape(7, 1)})
        s = buf.sample(16)
        assert s["obs"].shape == (16, 1)
        assert set(np.unique(s["obs"])) <= set(np.arange(7.0))
        buf.update_priorities(s["indices"], np.abs(s["obs"][:, 0]) + 0.1)
        s2 = buf.sample(16)
        assert s2["obs"].shape == (16, 1)


class _TwoAgentBitEnv:
    """Cooperative test env on the MultiAgentEnv dict contract: each agent
    observes a 4-dim context encoding a target bit; reward 1 for matching
    it. Agent a1's bit is the NEGATION of a0's, so a shared policy cannot
    ace both — per-agent policies must specialize."""

    action_space_n = 2

    def __init__(self, episode_len=16, seed=0):
        self._len = episode_len
        self._rng = np.random.default_rng(seed)
        self._t = 0
        self._bit = 0

    def _obs(self):
        b0 = float(self._bit)
        return {
            "a0": np.array([b0, 1 - b0, 1.0, 0.0], np.float32),
            "a1": np.array([b0, 1 - b0, 0.0, 1.0], np.float32),
        }

    def reset(self, seed=None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self._t = 0
        self._bit = int(self._rng.integers(0, 2))
        return self._obs(), {}

    def step(self, actions):
        rewards = {
            "a0": float(actions["a0"] == self._bit),
            "a1": float(actions["a1"] == 1 - self._bit),
        }
        self._t += 1
        done = self._t >= self._len
        self._bit = int(self._rng.integers(0, 2))
        terms = {"a0": done, "a1": done, "__all__": done}
        truncs = {"a0": False, "a1": False, "__all__": False}
        return self._obs(), rewards, terms, truncs, {}

    def close(self):
        pass


class TestMultiAgent:
    def _policies(self):
        from ray_tpu.rllib import RLModuleSpec

        spec = RLModuleSpec(observation_dim=4, action_dim=2, hidden=(32,))
        return {"p0": spec, "p1": spec}

    def test_runner_groups_by_policy(self, ray_start_regular):
        from ray_tpu.rllib.multi_agent import MultiAgentEnvRunner

        runner = MultiAgentEnvRunner(
            lambda: _TwoAgentBitEnv(episode_len=8),
            policies=self._policies(),
            policy_mapping_fn=lambda a: "p0" if a == "a0" else "p1",
            seed=0)
        out = runner.sample(24)
        trajs = out["trajectories"]
        assert set(trajs) == {"p0", "p1"}
        assert trajs["p0"] and trajs["p1"]
        total = sum(len(t["rewards"]) for t in trajs["p0"])
        assert total == 24  # one agent per policy, one step per env step
        assert out["num_episodes"] >= 2  # 24 steps / 8-step episodes
        t = trajs["p0"][0]
        assert t["obs"].shape[1] == 4
        assert len(t["actions"]) == len(t["logp"]) == len(t["values"])

    def test_multi_agent_ppo_learns_both_policies(self, ray_start_regular):
        """Learning gate: per-agent policies must specialize (a1's target
        is the negation of a0's) and lift the joint return toward the
        32-per-episode max."""
        from ray_tpu.rllib import MultiAgentPPOConfig

        algo = (MultiAgentPPOConfig()
                .environment(lambda: _TwoAgentBitEnv(episode_len=16))
                .multi_agent(
                    policies=self._policies(),
                    policy_mapping_fn=lambda a: "p0" if a == "a0" else "p1")
                .training(rollout_fragment_length=256, num_sgd_iter=4,
                          minibatch_size=64, lr=3e-3, entropy_coeff=0.0,
                          seed=0)
                .build())
        try:
            first, best = None, -np.inf
            for _ in range(12):
                r = algo.train()
                ret = r["episode_return_mean"]
                if not np.isnan(ret):
                    first = ret if first is None else first
                    best = max(best, ret)
                if best >= 28.0:
                    break
            # Random play averages 16 (half right); learned play nears 32.
            assert best >= 26.0, (first, best)
        finally:
            algo.stop()

    def test_multi_agent_checkpoint_roundtrip(self, ray_start_regular, tmp_path):
        from ray_tpu.rllib import MultiAgentPPOConfig

        def build(seed):
            return (MultiAgentPPOConfig()
                    .environment(lambda: _TwoAgentBitEnv(episode_len=8))
                    .multi_agent(
                        policies=self._policies(),
                        policy_mapping_fn=lambda a: "p0" if a == "a0" else "p1")
                    .training(rollout_fragment_length=32, seed=seed)
                    .build())

        algo = build(0)
        try:
            algo.train()
            path = algo.save(str(tmp_path / "ma_ck"))
            algo2 = build(9)
            try:
                algo2.restore(path)
                for pid in ("p0", "p1"):
                    for a, b in zip(
                            jax.tree.leaves(algo.learners[pid].get_weights()),
                            jax.tree.leaves(algo2.learners[pid].get_weights())):
                        np.testing.assert_array_equal(a, b)
            finally:
                algo2.stop()
        finally:
            algo.stop()


class TestAPPO:
    def test_appo_clipped_surrogate_differs_from_impala(self):
        """APPOLearner = ImpalaLearner with the PPO clip: at large policy
        divergence the clipped loss must differ from (and be bounded vs)
        the raw pg loss."""
        from ray_tpu.rllib import APPOLearner, ImpalaLearner, RLModuleSpec

        spec = RLModuleSpec(observation_dim=4, action_dim=2, hidden=(16,))
        cfg = {"lr": 1e-3, "gamma": 0.99, "clip_param": 0.2,
               "vf_loss_coeff": 0.5, "entropy_coeff": 0.0, "grad_clip": 40.0}
        appo = APPOLearner(spec, cfg, seed=0)
        imp = ImpalaLearner(spec, cfg, seed=0)
        T, N = 8, 4
        rng = np.random.default_rng(0)
        batch = {
            "obs": rng.normal(size=(T, N, 4)).astype(np.float32),
            "actions": rng.integers(0, 2, (T, N)).astype(np.float32),
            # VERY off-policy behavior logp -> ratios far outside the clip
            "logp": np.full((T, N), -3.0, np.float32),
            "rewards": rng.normal(size=(T, N)).astype(np.float32),
            "terminateds": np.zeros((T, N), np.float32),
            "valids": np.ones((T, N), np.float32),
            "bootstrap_obs": rng.normal(size=(N, 4)).astype(np.float32),
        }
        la = float(appo.loss_fn(appo.params, {k: jnp.asarray(v)
                                              for k, v in batch.items()}))
        li = float(imp.loss_fn(imp.params, {k: jnp.asarray(v)
                                            for k, v in batch.items()}))
        assert np.isfinite(la) and np.isfinite(li)
        assert abs(la - li) > 1e-4  # the clip actually engaged

    def test_appo_learns_cartpole(self, ray_start_regular):
        import gymnasium as gym

        from ray_tpu.rllib import APPOConfig

        algo = (APPOConfig()
                .environment(lambda: gym.make("CartPole-v1"))
                .env_runners(num_env_runners=2, num_envs_per_env_runner=4)
                .training(rollout_fragment_length=64, lr=5e-3,
                          entropy_coeff=0.005, clip_param=0.3, seed=0)
                .build())
        try:
            first, best = None, -np.inf
            for _ in range(30):
                r = algo.train()
                ret = r["episode_return_mean"]
                if not np.isnan(ret):
                    first = ret if first is None else first
                    best = max(best, ret)
                if best >= 120.0:
                    break
            assert first is not None
            assert best >= max(first * 1.5, 60.0), (first, best)
        finally:
            algo.stop()


class TestCQL:
    def _pendulum_corpus(self, n=2000, seed=0):
        """Mediocre-policy Pendulum transitions (random + proportional
        controller mix) — enough signal for offline learning."""
        import gymnasium as gym

        env = gym.make("Pendulum-v1")
        rng = np.random.default_rng(seed)
        cols = {k: [] for k in ("obs", "actions", "rewards", "next_obs",
                                "terminateds")}
        obs, _ = env.reset(seed=seed)
        for i in range(n):
            if rng.random() < 0.5:
                a = rng.uniform(-2.0, 2.0, size=(1,)).astype(np.float32)
            else:
                # crude stabilizer: torque against angular velocity
                a = np.clip(-1.5 * obs[2:3], -2.0, 2.0).astype(np.float32)
            nobs, r, term, trunc, _ = env.step(a)
            cols["obs"].append(np.asarray(obs, np.float32))
            cols["actions"].append(a)
            cols["rewards"].append(np.float32(r / 10.0))  # scale rewards
            cols["next_obs"].append(np.asarray(nobs, np.float32))
            cols["terminateds"].append(np.float32(term))
            obs = nobs
            if term or trunc:
                obs, _ = env.reset(seed=seed + i)
        env.close()
        return {k: np.stack(v) for k, v in cols.items()}

    @pytest.mark.slow  # a learning curve (PR 50): `-m slow` runs it
    def test_cql_penalty_pushes_down_ood_q(self, ray_start_regular):
        """The conservative term must leave Q(s, a_random) BELOW
        Q(s, a_data) after training — the defining CQL property."""
        from ray_tpu.rllib import CQLConfig

        data = self._pendulum_corpus(1500, seed=0)
        algo = CQLConfig(
            dataset=data, observation_dim=3, action_dim=1,
            action_low=-2.0, action_high=2.0, hidden=(32, 32),
            train_batch_size=128, updates_per_iteration=40,
            cql_alpha=5.0, lr=1e-3, seed=0,
        ).build()
        for _ in range(6):
            r = algo.train()
        assert np.isfinite(r["loss"])

        m = algo.module
        qp = algo.learner.params["q1"]
        obs = jnp.asarray(data["obs"][:256])
        q_data = np.asarray(m.q_value(qp, obs,
                                      jnp.asarray(data["actions"][:256])))
        rng = np.random.default_rng(1)
        rand_a = jnp.asarray(rng.uniform(-2, 2, (256, 1)).astype(np.float32))
        q_rand = np.asarray(m.q_value(qp, obs, rand_a))
        assert q_rand.mean() < q_data.mean(), (q_rand.mean(), q_data.mean())

    def test_cql_checkpoint_roundtrip(self, ray_start_regular, tmp_path):
        from ray_tpu.rllib import CQLConfig

        data = self._pendulum_corpus(300, seed=2)
        cfg = dict(dataset=data, observation_dim=3, action_dim=1,
                   action_low=-2.0, action_high=2.0, hidden=(16,),
                   train_batch_size=64, updates_per_iteration=4)
        algo = CQLConfig(**cfg, seed=0).build()
        algo.train()
        path = algo.save(str(tmp_path / "cql_ck"))
        algo2 = CQLConfig(**cfg, seed=7).build()
        algo2.restore(path)
        for a, b in zip(jax.tree.leaves(algo.learner.params),
                        jax.tree.leaves(algo2.learner.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ev = algo2.evaluate(lambda: __import__("gymnasium").make("Pendulum-v1"),
                            num_episodes=2)
        assert np.isfinite(ev["episode_return_mean"])
