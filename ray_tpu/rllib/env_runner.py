"""EnvRunner — vectorized environment sampling actors.

Analog of the reference's ``rllib/env/single_agent_env_runner.py:101
sample``: each runner holds a vectorized gymnasium env + a local copy of the
module params, steps envs with jitted forward passes, and returns columnar
sample batches (numpy — they cross the object store to the learners).
Episode returns are tracked per sub-env for metrics.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu.rllib.rl_module import RLModule, RLModuleSpec, spec_for_env


class SingleAgentEnvRunner:
    def __init__(
        self,
        env_creator: Callable[[], Any],
        *,
        num_envs: int = 1,
        seed: int = 0,
        spec: Optional[RLModuleSpec] = None,
        module_factory: Optional[Callable[[RLModuleSpec], Any]] = None,
        inference: Optional[Any] = None,
    ):
        import gymnasium as gym

        self._envs = gym.vector.SyncVectorEnv(
            [self._thunk(env_creator, seed + i) for i in range(num_envs)]
        )
        self.num_envs = num_envs
        probe = env_creator()
        self.spec = spec or spec_for_env(probe)
        probe.close()
        # Algorithms with non-actor-critic policies (SAC's tanh-squashed
        # Gaussian) plug in their own module; the contract is
        # ``init_params`` / ``sample_action(params, obs, key)`` /
        # ``forward_inference`` (reference: RLModuleSpec.module_class).
        self.module = (module_factory(self.spec) if module_factory
                       else RLModule(self.spec))
        # Env-runner inference is tiny and latency-bound: pin it to host CPU
        # (committed args steer jit placement). The TPU belongs to learners —
        # shipping a 4-float CartPole obs across the interconnect per step
        # would make sampling interconnect-latency-bound.
        self._device = jax.local_devices(backend="cpu")[0]
        self._params = jax.device_put(
            self.module.init_params(jax.random.key(seed)), self._device
        )
        self._key = jax.device_put(jax.random.key(seed + 10_000), self._device)
        self._sample_fn = jax.jit(self.module.sample_action)
        # Value-based algorithms (DQN family) explore epsilon-greedily over
        # the argmax policy instead of sampling the softmax
        # (rllib/utils/exploration/epsilon_greedy.py analog).
        self._greedy = False
        self._epsilon = 0.0
        self._np_rng = np.random.default_rng(seed + 20_000)
        self._greedy_fn = jax.jit(
            lambda p, o: jnp.argmax(
                self.module.forward_inference(p, o)["action_dist_inputs"],
                axis=-1))
        # Sebulba mode: an InferenceActor handle. The runner keeps its key
        # stream (split per step, key data shipped with the obs) so the
        # sampled actions are bitwise-identical to runner-local inference;
        # only the forward pass moves to the shared, batched actor.
        self._inference = inference
        self._obs, _ = self._envs.reset(seed=seed)
        # gymnasium >=1.0 vector envs autoreset on the step AFTER done
        # (NEXT_STEP mode): that step ignores the action and returns the new
        # episode's reset obs with reward 0.  Transitions recorded on such
        # steps are junk (action never executed) and must be masked out of
        # GAE and the loss; this tracks which sub-envs are in that state.
        self._autoreset = np.zeros(num_envs, dtype=bool)
        self._ep_returns = np.zeros(num_envs)
        self._ep_lens = np.zeros(num_envs, dtype=np.int64)
        self._completed: List[float] = []
        self._completed_lens: List[int] = []

    @staticmethod
    def _thunk(creator, seed):
        def make():
            env = creator()
            env.reset(seed=seed)
            return env

        return make

    # -- weights sync (reference: WorkerSet weight broadcast) ----------------
    def set_weights(self, params) -> bool:
        self._params = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), self._device), params
        )
        return True

    def get_weights(self):
        return jax.tree.map(np.asarray, self._params)

    def set_exploration(self, epsilon: float, greedy: bool = True) -> bool:
        """Epsilon-greedy exploration for value-based learners: with prob
        epsilon a uniform random action, else argmax over the head outputs
        (interpreted as Q-values)."""
        assert self.spec.discrete, "epsilon-greedy needs a discrete space"
        self._epsilon = float(epsilon)
        self._greedy = bool(greedy)
        return True

    # -- sampling ------------------------------------------------------------
    def sample(self, num_steps: int) -> Dict[str, np.ndarray]:
        """Collect ``num_steps`` per sub-env; returns a columnar batch with
        bootstrap values for GAE (shape [T, N, ...] flattened to [T*N, ...]
        AFTER advantage computation by the algorithm — kept 2D here)."""
        T, N = num_steps, self.num_envs
        # Pixel obs stay uint8 end-to-end (the conv torso casts /255 on
        # device) — 4x less object-plane traffic than float32.
        obs_dtype = np.uint8 if self.spec.conv else np.float32
        obs_buf = np.zeros((T, N, self.spec.observation_dim), obs_dtype)
        act_shape = (T, N) if self.spec.discrete else (T, N, self.spec.action_dim)
        act_buf = np.zeros(act_shape, np.float32)
        logp_buf = np.zeros((T, N), np.float32)
        val_buf = np.zeros((T, N), np.float32)
        rew_buf = np.zeros((T, N), np.float32)
        done_buf = np.zeros((T, N), np.float32)
        valid_buf = np.ones((T, N), np.float32)

        for t in range(T):
            self._key, sub = jax.random.split(self._key)
            obs = np.asarray(self._obs, obs_dtype).reshape(N, -1)
            # numpy → CPU device directly: jnp.asarray would materialize on
            # the DEFAULT device first (a host→TPU transfer per env step when
            # the default device is a TPU)
            if self._inference is not None:
                import ray_tpu

                key_data = (None if self._greedy
                            else jax.device_get(jax.random.key_data(sub)))
                action_np, logp_np, val_np = ray_tpu.get(
                    self._inference.infer.remote(obs, key_data, self._greedy))
                if self._greedy and self._epsilon > 0:
                    explore = self._np_rng.random(N) < self._epsilon
                    randoms = self._np_rng.integers(
                        0, self.spec.action_dim, N)
                    action_np = np.where(explore, randoms, action_np)
            elif self._greedy:
                action = self._greedy_fn(
                    self._params, jax.device_put(obs, self._device))
                action_np = jax.device_get(action)  # the step's one sync
                logp_np = np.zeros(N, np.float32)
                val_np = np.zeros(N, np.float32)
                if self._epsilon > 0:
                    explore = self._np_rng.random(N) < self._epsilon
                    randoms = self._np_rng.integers(
                        0, self.spec.action_dim, N)
                    action_np = np.where(explore, randoms, action_np)
            else:
                action, logp, value = self._sample_fn(
                    self._params, jax.device_put(obs, self._device), sub
                )
                # one batched fetch per env step instead of three syncs
                action_np, logp_np, val_np = jax.device_get(
                    (action, logp, value))
            env_action = action_np.astype(np.int64) if self.spec.discrete else action_np
            next_obs, reward, terminated, truncated, _ = self._envs.step(env_action)
            done = np.logical_or(terminated, truncated)

            obs_buf[t] = obs
            act_buf[t] = action_np
            logp_buf[t] = logp_np
            val_buf[t] = val_np
            rew_buf[t] = reward
            # GAE must not bootstrap across true terminations; truncations
            # keep bootstrapping (the obs recorded on the autoreset step is
            # the truncated episode's FINAL obs, so its value is exactly the
            # truncation bootstrap — see compute_gae's valids handling).
            done_buf[t] = terminated.astype(np.float32)
            valid_buf[t] = (~self._autoreset).astype(np.float32)
            self._autoreset = done.copy()

            live = (valid_buf[t] > 0)
            self._ep_returns += reward * live
            self._ep_lens += live.astype(np.int64)
            for i in np.nonzero(done)[0]:
                self._completed.append(float(self._ep_returns[i]))
                self._completed_lens.append(int(self._ep_lens[i]))
                self._ep_returns[i] = 0.0
                self._ep_lens[i] = 0
            self._obs = next_obs

        # bootstrap value of the final observation
        last_obs = np.asarray(self._obs, obs_dtype).reshape(N, -1)
        if self._inference is not None:
            import ray_tpu

            last_val = np.asarray(ray_tpu.get(
                self._inference.values.remote(last_obs)))
        else:
            out = self.module.forward_inference(
                self._params, jax.device_put(last_obs, self._device)
            )
            last_val = jax.device_get(out["vf_preds"])

        return {
            "obs": obs_buf,
            "actions": act_buf,
            "logp": logp_buf,
            "values": val_buf,
            "rewards": rew_buf,
            "terminateds": done_buf,
            "valids": valid_buf,
            "bootstrap_value": last_val,
            # Off-policy learners (V-trace) re-evaluate the bootstrap under
            # the CURRENT policy — they need the obs, not our stale value.
            "bootstrap_obs": last_obs,
        }

    def sample_dag(self, payload: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """One rollout-lane tick (rllib/rollout_lanes.py). A lane-parked
        actor's execution thread lives inside the DAG loop, so ordinary
        method calls (``set_weights``/``get_metrics``) would queue behind
        it forever — weight updates ride the tick payload in and episode
        metrics ride the fragment out instead."""
        weights = payload.get("weights")
        if weights is not None:
            self.set_weights(weights)
        fragment = self.sample(int(payload["num_steps"]))
        fragment["metrics"] = self.get_metrics()
        return fragment

    def ping(self) -> bool:
        """Liveness probe for the driver's respawn path."""
        return True

    def get_metrics(self) -> Dict[str, float]:
        completed, self._completed = self._completed, []
        lens, self._completed_lens = self._completed_lens, []
        return {
            "episode_return_mean": float(np.mean(completed)) if completed else float("nan"),
            "episode_len_mean": float(np.mean(lens)) if lens else float("nan"),
            "num_episodes": float(len(completed)),
        }

    def stop(self) -> None:
        self._envs.close()
