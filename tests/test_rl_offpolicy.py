"""RL library tests: the replay-buffer family (DQN, prioritized replay, SAC,
TD3) and learning from recorded rollouts (BC, CQL)."""

import numpy as np
import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu import rl


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4)
    yield
    ca.shutdown()


def test_dqn_learns_cartpole():
    algo = (
        rl.AlgorithmConfig("DQN")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(
            lr=1e-3,
            rollout_length=64,
            epsilon_decay=0.9,
            updates_per_iteration=64,
            seed=0,
        )
        .build()
    )
    try:
        rets = []
        for _ in range(15):
            result = algo.train()
            if "episode_return_mean" in result:
                rets.append(result["episode_return_mean"])
        # sampled returns must trend up as epsilon anneals + q-net learns
        assert max(rets[-3:]) > np.mean(rets[:3]) * 1.5, rets
    finally:
        algo.stop()


def test_pendulum_env_basics():
    env = rl.Pendulum()
    obs = env.reset(seed=0)
    assert obs.shape == (3,)
    obs, r, done, _ = env.step(np.array([0.5], np.float32))
    assert r <= 0.0 and not done  # cost-based reward
    assert env.continuous and env.action_dim == 1


def test_sac_learns_pendulum():
    algo = (
        rl.AlgorithmConfig("SAC")
        .environment("Pendulum-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(
            lr=3e-3,
            rollout_length=32,
            train_batch_size=256,
            updates_per_iteration=64,
            seed=0,
        )
        .build()
    )
    try:
        first_eval = algo.evaluate(3)
        for _ in range(60):
            result = algo.train()
        final_eval = algo.evaluate(3)
        # random policy sits near -1300; a learning SAC clears -700 easily
        assert final_eval > max(first_eval, -700.0), (first_eval, final_eval)
        assert "critic_loss" in result and np.isfinite(result["critic_loss"])
    finally:
        algo.stop()


def test_offline_bc_clones_policy(tmp_path):
    """Record rollouts from a PPO-trained policy, then behavior-clone them
    offline; the clone must clearly beat random play (rllib BC workflow)."""
    algo = (
        rl.AlgorithmConfig("PPO")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(lr=3e-3, rollout_length=128, epochs=6, seed=3)
        .build()
    )
    try:
        for _ in range(10):
            algo.train()
        expert_eval = algo.evaluate(3)
        path = rl.record_rollouts(algo, str(tmp_path / "rollouts"), num_iterations=2)
    finally:
        algo.stop()

    reader = rl.RolloutReader(path)
    assert reader.num_rows >= 2 * 2 * 4 * 128
    learner = rl.train_bc(path, obs_dim=4, num_actions=2, num_updates=300, seed=0)
    # the NLL floor is the (stochastic) expert's own action entropy, so only
    # require convergence into that ballpark
    assert learner.last_stats["bc_loss"] < 0.7

    # greedy clone rollout
    import jax
    import jax.numpy as jnp

    env = rl.CartPole()
    logits_fn = jax.jit(learner.module.logits)
    total = 0.0
    for ep in range(3):
        obs = env.reset(seed=2000 + ep)
        done, ret = False, 0.0
        while not done:
            out = np.asarray(logits_fn(learner.params, jnp.asarray(obs[None])))[0]
            obs, r, done, _ = env.step(int(out.argmax()))
            ret += r
        total += ret
    clone_eval = total / 3
    assert clone_eval > 80.0, (expert_eval, clone_eval)


def test_offline_cql_beats_random(tmp_path):
    """CQL on logged expert data: the conservative Q policy clearly beats
    random play without ever touching the environment online."""
    algo = (
        rl.AlgorithmConfig("PPO")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(lr=3e-3, rollout_length=128, epochs=6, seed=3)
        .build()
    )
    try:
        for _ in range(10):
            algo.train()
        path = rl.record_rollouts(algo, str(tmp_path / "cql_data"), num_iterations=2)
    finally:
        algo.stop()

    learner = rl.train_cql(path, obs_dim=4, num_actions=2, num_updates=800, seed=0)
    assert np.isfinite(learner.last_stats["loss"])
    assert learner.last_stats["cql_penalty"] < 5.0  # regularizer converging

    import jax
    import jax.numpy as jnp

    env = rl.CartPole()
    q_fn = jax.jit(learner.module.q_values)
    total = 0.0
    for ep in range(3):
        obs = env.reset(seed=3000 + ep)
        done, ret = False, 0.0
        while not done:
            q = np.asarray(q_fn(learner.params, jnp.asarray(obs[None])))[0]
            obs, r, done, _ = env.step(int(q.argmax()))
            ret += r
        total += ret
    assert total / 3 > 80.0, total / 3


def test_prioritized_buffer_mechanics():
    """Sum-tree sampling is proportional to priority^alpha; IS weights
    correct the induced bias; update_priorities redirects sampling mass
    (rllib prioritized_episode_buffer semantics, transition-level)."""
    buf = rl.PrioritizedReplayBuffer(
        capacity=128, obs_dim=2, seed=0, alpha=1.0, beta=1.0
    )
    n = 100
    obs = np.arange(2 * n, dtype=np.float32).reshape(n, 2)
    buf.add_batch(obs, np.zeros(n, np.int32), np.zeros(n, np.float32),
                  np.zeros(n, np.float32), obs)
    assert len(buf) == n
    # all priorities equal -> near-uniform sampling, weights all 1
    s = buf.sample(64)
    assert s["weights"].max() == 1.0 and s["weights"].min() > 0.99
    # spike one index's priority: it must dominate samples
    buf.update_priorities(np.arange(n), np.full(n, 0.01))
    buf.update_priorities(np.array([7]), np.array([100.0]))
    counts = np.zeros(n)
    for _ in range(20):
        s = buf.sample(64)
        for i in s["indices"]:
            counts[i] += 1
    assert counts[7] > counts.sum() * 0.8, counts[7] / counts.sum()
    # and its IS weight is the smallest (most-oversampled => most down-weighted)
    s = buf.sample(64)
    w_spiked = s["weights"][s["indices"] == 7]
    assert len(w_spiked) and w_spiked.min() <= s["weights"].min() + 1e-9


def test_dqn_per_prioritizes_surprising_transitions():
    """DQN + PER end to end: the learner's td_abs feeds back into the
    buffer, and sampling concentrates on high-TD transitions.  Seeds pinned;
    asserts the mechanism (priorities diverge from uniform), plus learning
    still happens on CartPole with PER on."""
    algo = (
        rl.AlgorithmConfig("DQN")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(
            lr=1e-3,
            rollout_length=64,
            epsilon_decay=0.9,
            updates_per_iteration=64,
            replay="prioritized",
            seed=0,
        )
        .build()
    )
    try:
        rets = []
        for _ in range(15):
            result = algo.train()
            if "episode_return_mean" in result:
                rets.append(result["episode_return_mean"])
        assert max(rets[-3:]) > np.mean(rets[:3]) * 1.5, rets
        # the tree must have differentiated: spread between the most and
        # least surprising stored transition
        leaves = algo.buffer.tree.tree[algo.buffer.tree.n_leaves:][: len(algo.buffer)]
        assert leaves.max() > leaves[leaves > 0].min() * 10, (
            leaves.max(), leaves.min())
    finally:
        algo.stop()


def test_td3_learns_pendulum():
    """TD3 (twin critics, target-policy smoothing, delayed actor updates —
    rllib/algorithms/td3) must improve Pendulum within a small budget, like
    the SAC test: returns rise from the random-policy floor (~-1300)."""
    algo = (
        rl.AlgorithmConfig("TD3")
        .environment("Pendulum-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(
            lr=3e-3,
            rollout_length=32,
            updates_per_iteration=256,  # ~1 update per env step (TD3 wants density)
            train_batch_size=256,
            exploration_noise=0.2,
            seed=0,
        )
        .build()
    )
    try:
        first_eval = algo.evaluate(3)
        for _ in range(60):  # same budget as the SAC pendulum test
            result = algo.train()
        final_eval = algo.evaluate(3)
        # random policy sits near -1300; a learning TD3 clears -800
        assert final_eval > max(first_eval, -800.0), (first_eval, final_eval)
        assert np.isfinite(result["critic_loss"])
    finally:
        algo.stop()
