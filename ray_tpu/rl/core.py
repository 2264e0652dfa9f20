"""Shared RL infrastructure: networks, replay, Algorithm base.

Reference: rllib's Algorithm (rllib/algorithms/algorithm.py:554 setup /
:813 step), ReplayBuffer (rllib/utils/replay_buffers/), and the
RolloutWorker fleet pattern (rllib/evaluation/worker_set.py). The learner
update is a single jitted function per algorithm (the TPU-native shape of
rllib's Learner, core/learner/learner.py) — batched, static shapes, no
Python in the step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

import ray_tpu


# --- tiny pure-JAX nets ------------------------------------------------------


def dense_init(key, i, o, scale: float = None):
    import jax

    s = (2.0 / i) ** 0.5 if scale is None else scale
    return {"w": jax.random.normal(key, (i, o)) * s,
            "b": jax.numpy.zeros((o,))}


def reward_to_go(batch_or_rewards, gamma: float, dones=None):
    """Discounted reward-to-go, resetting at dones (shared by PG/MARWIL;
    ref: postprocessing.compute_advantages with use_critic=False)."""
    import numpy as np

    if dones is None:
        rews = batch_or_rewards["rewards"]
        dones = batch_or_rewards["dones"]
    else:
        rews = batch_or_rewards
    out = np.zeros_like(rews, dtype=np.float32)
    running = 0.0
    for t in range(len(rews) - 1, -1, -1):
        running = rews[t] + gamma * running * (1.0 - dones[t])
        out[t] = running
    return out


def rollout_result(timesteps_total: int, worker_stats, aux) -> dict:
    """The standard on-policy result dict (shared by A2C/A3C/PG)."""
    import numpy as np

    eps_done = [s for s in worker_stats if s["episodes"]]
    return {
        "timesteps_total": timesteps_total,
        "episode_return_mean": float(np.mean(
            [s["mean_return"] for s in eps_done])) if eps_done else 0.0,
        "episodes_total": sum(s["episodes"] for s in worker_stats),
        **{k: float(v) for k, v in aux.items()},
    }


def mlp_init(key, sizes: List[int], out_scale: float = None):
    import jax

    keys = jax.random.split(key, len(sizes) - 1)
    layers = []
    for n, (i, o) in enumerate(zip(sizes[:-1], sizes[1:])):
        last = n == len(sizes) - 2
        layers.append(dense_init(keys[n], i, o,
                                 out_scale if last else None))
    return layers


def mlp_forward(layers, x, final_activation=False):
    import jax.numpy as jnp

    for n, layer in enumerate(layers):
        x = x @ layer["w"] + layer["b"]
        if n < len(layers) - 1 or final_activation:
            x = jnp.tanh(x)
    return x


# --- rollout sampling --------------------------------------------------------


# Rollout actors must never grab the TPU: the learner owns it, and a
# worker that initializes jax on the chip deadlocks the single-chip bench
# box. process_env_vars applies at worker-process spawn, BEFORE jax import
# (runtime_env.py) — EnvSampler's in-process setdefault alone is too late
# when the worker pool prestarted a process that already imported jax.
CPU_WORKER_ENV = {"process_env_vars": {"JAX_PLATFORMS": "cpu"}}


def make_env(env_name: str, env_config: Optional[dict] = None):
    """Construct an env. "module:Class" names import and instantiate
    directly (no registry round-trip — works in any worker process, e.g.
    "ray_tpu.rl.pixel_env:PixelCatcher"); everything else goes through
    gymnasium.make (ref: rllib env_creator resolution in
    rllib/env/utils.py)."""
    if ":" in env_name:
        import importlib

        mod_name, cls_name = env_name.split(":", 1)
        cls = getattr(importlib.import_module(mod_name), cls_name)
        return cls(**(env_config or {}))
    import gymnasium as gym

    return gym.make(env_name, **(env_config or {}))


class EnvSampler:
    """Shared env-loop plumbing for rollout actors: env construction,
    episode-return accounting, reset handling (ref: rollout_worker.py
    sample loop bookkeeping). Subclasses implement action selection."""

    def __init__(self, env_name: str, seed: int = 0,
                 env_config: Optional[dict] = None):
        import os

        os.environ.setdefault("JAX_PLATFORMS", "cpu")

        self.env = make_env(env_name, env_config)
        self.seed = seed
        self.obs, _ = self.env.reset(seed=seed)
        self.steps = 0
        self.episode_return = 0.0
        self.completed: List[float] = []

    def step_env(self, action):
        """One env step with episode bookkeeping; returns
        (prev_obs, reward, terminated, truncated, next_obs) where next_obs
        is the pre-reset successor (what TD targets need)."""
        prev = self.obs
        nobs, rew, term, trunc, _ = self.env.step(action)
        successor = nobs
        self.episode_return += float(rew)
        self.steps += 1
        if term or trunc:
            self.completed.append(self.episode_return)
            self.episode_return = 0.0
            nobs, _ = self.env.reset()
        self.obs = nobs
        return prev, float(rew), bool(term), bool(trunc), successor

    def episode_stats(self) -> Dict[str, float]:
        return episode_stats_from(self.completed)

    def sample_transitions(self, select_action,
                           num_steps: int) -> Dict[str, np.ndarray]:
        """Collect an off-policy transition batch
        {obs, actions, rewards, dones, next_obs}; action choice is the
        only per-algorithm part (shared by the SAC/TD3 workers)."""
        obs_b, act_b, rew_b, done_b, nobs_b = [], [], [], [], []
        for _ in range(num_steps):
            action = select_action(self.obs)
            prev, rew, term, _trunc, nobs = self.step_env(action)
            obs_b.append(np.asarray(prev, np.float32))
            act_b.append(np.asarray(action, np.float32))
            rew_b.append(rew)
            done_b.append(float(term))
            nobs_b.append(np.asarray(nobs, np.float32))
        return {"obs": np.stack(obs_b), "actions": np.stack(act_b),
                "rewards": np.asarray(rew_b, np.float32),
                "dones": np.asarray(done_b, np.float32),
                "next_obs": np.stack(nobs_b)}


def episode_stats_from(completed: List[float]) -> Dict[str, float]:
    """Windowed episode-return stats shared by every rollout worker."""
    rets = completed[-20:]
    return {"episodes": len(completed),
            "mean_return": float(np.mean(rets)) if rets else 0.0}


# --- replay buffer -----------------------------------------------------------


class ReplayBuffer:
    """Uniform FIFO replay (ref: rllib/utils/replay_buffers/replay_buffer.py).
    Process-local; the off-policy trainers own one in the driver. For a
    distributed variant wrap it in an actor via `as_actor()`."""

    def __init__(self, capacity: int, seed: int = 0):
        self.capacity = capacity
        self._storage: Dict[str, np.ndarray] = {}
        self._idx = 0
        self._size = 0
        self._rng = np.random.default_rng(seed)

    def add_batch(self, batch: Dict[str, np.ndarray]):
        n = len(next(iter(batch.values())))
        if not self._storage:
            for k, v in batch.items():
                v = np.asarray(v)
                self._storage[k] = np.zeros((self.capacity,) + v.shape[1:],
                                            v.dtype)
        for k, v in batch.items():
            v = np.asarray(v)
            idx = (self._idx + np.arange(n)) % self.capacity
            self._storage[k][idx] = v
        self._idx = (self._idx + n) % self.capacity
        self._size = min(self._size + n, self.capacity)

    def sample(self, batch_size: int) -> Dict[str, np.ndarray]:
        idx = self._rng.integers(0, self._size, batch_size)
        return {k: v[idx] for k, v in self._storage.items()}

    def __len__(self):
        return self._size


@ray_tpu.remote
class ReplayActor:
    """Replay buffer as an actor, for async fill/sample fan-in
    (ref: rllib distributed replay in APEX)."""

    def __init__(self, capacity: int, seed: int = 0):
        self.buf = ReplayBuffer(capacity, seed)

    def add_batch(self, batch):
        self.buf.add_batch(batch)
        return len(self.buf)

    def sample(self, batch_size: int):
        if len(self.buf) < batch_size:
            return None
        return self.buf.sample(batch_size)

    def size(self):
        return len(self.buf)


# --- Algorithm base ----------------------------------------------------------


class Algorithm:
    """Minimal Trainable-compatible base (ref: Algorithm is a Tune
    Trainable; tune.Tuner can drive any subclass via the function API:
    `lambda cfg: loop over algo.train()`)."""

    def __init__(self, config):
        self.config = config
        self.iteration = 0
        self._setup(config)

    def _setup(self, config):
        raise NotImplementedError

    def training_step(self) -> Dict[str, Any]:
        raise NotImplementedError

    def train(self) -> Dict[str, Any]:
        t0 = time.time()
        result = self.training_step()
        self.iteration += 1
        result.setdefault("training_iteration", self.iteration)
        result.setdefault("time_this_iter_s", time.time() - t0)
        return result

    def save(self) -> Dict[str, Any]:
        import jax

        return {"params": jax.device_get(self.get_weights()),
                "iteration": self.iteration}

    def restore(self, ckpt: Dict[str, Any]):
        self.set_weights(ckpt["params"])
        self.iteration = ckpt.get("iteration", 0)

    def get_weights(self):
        raise NotImplementedError

    def set_weights(self, weights):
        raise NotImplementedError

    def stop(self):
        for w in getattr(self, "workers", []):
            try:
                ray_tpu.kill(w)
            except Exception:
                pass


def probe_connected_spec(env_name: str, env_config: Optional[dict],
                         connectors, seed: int = 0):
    """(obs_shape_after_connectors, n_actions) for a discrete-action env
    — the shared probe used by every actor-critic trainer (PPO/IMPALA/
    APPO/DDPPO) to size its policy net. Always closes the probe env."""
    from ray_tpu.rl.connectors import build_pipeline

    env = make_env(env_name, env_config)
    try:
        obs0, _ = env.reset(seed=seed)
        if not hasattr(env.action_space, "n"):
            raise ValueError(
                f"{env_name} is not discrete-action; this trainer family "
                "requires a Discrete action space")
        n_actions = int(env.action_space.n)
    finally:
        env.close()
    pipeline = build_pipeline(connectors)
    obs_shape = pipeline(np.asarray(obs0, np.float32)).shape
    return obs_shape, n_actions


def probe_env_spec(env_name: str, env_config: Optional[dict] = None):
    """(obs_dim, n_actions | None, act_dim | None, act_high)."""
    env = make_env(env_name, env_config)
    obs_dim = int(np.prod(env.observation_space.shape))
    n_actions = act_dim = act_high = None
    if hasattr(env.action_space, "n"):
        n_actions = int(env.action_space.n)
    else:
        act_dim = int(np.prod(env.action_space.shape))
        act_high = float(np.asarray(env.action_space.high).reshape(-1)[0])
    env.close()
    return obs_dim, n_actions, act_dim, act_high
