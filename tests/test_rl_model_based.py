"""RL library tests: DreamerV3, which learns in its world model's imagination."""

import pytest

import cluster_anywhere_tpu as ca


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4)
    yield
    ca.shutdown()


def test_dreamerv3_learns_cartpole_in_imagination():
    """DreamerV3 (rllib/algorithms/dreamerv3 role): the RSSM world model +
    imagination actor-critic must solve CartPole from ~55 real episodes —
    far fewer environment steps than the model-free algorithms use,
    the defining property of the algorithm.  Fully seeded; asserts the
    greedy policy beats 5x the random-policy return."""
    from cluster_anywhere_tpu.rl.dreamer import (
        DreamerConfig,
        evaluate_dreamer,
        train_dreamer,
    )
    from cluster_anywhere_tpu.rl.env import CartPole

    cfg = DreamerConfig(
        obs_dim=4, num_actions=2, ac_lr=3e-4, entropy=1e-2, horizon=15
    )
    learner = train_dreamer(
        CartPole, cfg=cfg, episodes=55, updates_per_episode=30, seed=0
    )
    score = evaluate_dreamer(learner, CartPole, 3)
    assert score > 150.0, (score, learner.episode_returns[-8:])
    # world-model sanity rides along: reward/continue heads converged
    assert learner.last_stats["rew_loss"] < 1.5
