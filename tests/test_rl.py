"""RL library tests (modeled on the reference's rllib learning tests,
compressed: PPO must improve on CartPole within a small budget): the
environments, the on-policy family, many agents, connectors, checkpoints."""

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


def test_cartpole_env_basics():
    env = rl.CartPole()
    obs = env.reset(seed=0)
    assert obs.shape == (4,)
    obs, r, done, _ = env.step(1)
    assert r == 1.0 and not done
    # random policy dies fast
    env.reset(seed=1)
    steps = 0
    rng = np.random.default_rng(0)
    done = False
    while not done and steps < 500:
        _, _, done, _ = env.step(int(rng.integers(2)))
        steps += 1
    assert steps < 200


def test_vector_env_autoreset():
    vec = rl.VectorEnv("CartPole-v1", 3, seed=0)
    for _ in range(250):
        vec.step(np.zeros(3, np.int32))  # constant action dies quickly
    m = vec.drain_metrics()
    assert m["episodes"] > 0
    assert m["episode_return_mean"] > 0


def test_gae_computation():
    T, N = 3, 2
    rollout = {
        "rewards": np.ones((T, N), np.float32),
        "values": np.zeros((T, N), np.float32),
        "dones": np.zeros((T, N)),
        "last_values": np.zeros(N, np.float32),
    }
    adv, ret = rl.compute_gae(rollout, gamma=1.0, lam=1.0)
    # undiscounted returns-to-go: [3, 2, 1] per env
    assert ret.reshape(T, N)[0, 0] == 3.0
    assert ret.reshape(T, N)[2, 0] == 1.0
    assert abs(adv.mean()) < 1e-6  # normalized


def test_ppo_learns_cartpole():
    algo = (
        rl.AlgorithmConfig("PPO")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(lr=3e-3, rollout_length=128, epochs=6, seed=3)
        .build()
    )
    try:
        first_eval = algo.evaluate(3)
        returns = []
        for _ in range(12):
            result = algo.train()
            if "episode_return_mean" in result:
                returns.append(result["episode_return_mean"])
        final_eval = algo.evaluate(3)
        # must clearly improve over the random-ish initial policy
        assert final_eval > max(first_eval * 2, 80.0), (first_eval, final_eval, returns)
    finally:
        algo.stop()


def test_checkpoint_roundtrip(tmp_path):
    algo = (
        rl.AlgorithmConfig("PPO")
        .environment("CartPole-v1")
        .env_runners(1, num_envs_per_runner=2)
        .training(rollout_length=32)
        .build()
    )
    try:
        algo.train()
        path = str(tmp_path / "ckpt")
        algo.save(path)
        before = algo.evaluate(2)
        algo2 = (
            rl.AlgorithmConfig("PPO")
            .environment("CartPole-v1")
            .env_runners(1, num_envs_per_runner=2)
            .build()
        )
        try:
            algo2.load(path)
            after = algo2.evaluate(2)
            assert before == after  # same weights -> same greedy rollouts
        finally:
            algo2.stop()
    finally:
        algo.stop()


def test_custom_env_registration():
    class TinyEnv(rl.Env):
        observation_dim = 2
        num_actions = 2

        def __init__(self):
            self.t = 0

        def reset(self, seed=None):
            self.t = 0
            return np.zeros(2, np.float32)

        def step(self, action):
            self.t += 1
            return (
                np.asarray([self.t / 10, action], np.float32),
                float(action),
                self.t >= 10,
                {},
            )

    rl.register_env("Tiny-v0", TinyEnv)
    algo = (
        rl.AlgorithmConfig("PPO")
        .environment("Tiny-v0")
        .env_runners(1, num_envs_per_runner=2)
        .training(rollout_length=20)
        .build()
    )
    try:
        result = algo.train()
        assert result["env_steps_this_iter"] == 40
    finally:
        algo.stop()


def test_impala_learns_cartpole():
    """IMPALA: async actor-learner with V-trace off-policy correction must
    improve on CartPole despite runners sampling with lagged weights."""
    algo = (
        rl.AlgorithmConfig("IMPALA")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(lr=2e-3, rollout_length=128, entropy_coeff=0.02, seed=5)
        .build()
    )
    try:
        first_eval = algo.evaluate(3)
        for _ in range(25):
            result = algo.train()
        assert result["training_iteration"] == 25
        assert "mean_rho" in result  # the V-trace path actually ran
        final_eval = algo.evaluate(3)
        assert final_eval > max(first_eval * 1.5, 60.0), (first_eval, final_eval)
    finally:
        algo.stop()


def test_multi_agent_env_contract():
    env = rl.RockPaperScissors()
    obs = env.reset(seed=0)
    assert set(obs) == {"player1", "player2"}
    obs, rew, dones, _ = env.step({"player1": 0, "player2": 2})  # rock beats scissors
    assert rew["player1"] == 1.0 and rew["player2"] == -1.0
    assert dones["__all__"] is False


def test_multi_agent_ppo_coordination():
    """Independent PPO with two separate policies learns to coordinate:
    mean per-step reward approaches 1 (both agents picking the same arm).
    One env runner: independent env copies pull the policy pair toward
    different coordination equilibria and stall symmetry breaking — an RL
    dynamics property of the game, not the runtime."""
    trainer = rl.MultiAgentPPO(
        rl.CoordinationGame,
        policies={"p0": {}, "p1": {}},
        policy_mapping_fn=lambda aid: "p0" if aid == "a0" else "p1",
        num_env_runners=1,
        rollout_length=64,
        lr=5e-3,
        seed=1,
    )
    try:
        returns = []
        for _ in range(25):
            m = trainer.train()
            if "episode_return_mean" in m:
                returns.append(m["episode_return_mean"])
        # episode_len=16; random play averages 8, coordination approaches 16
        assert returns[-1] > 12.0, returns[-5:]
        assert "p0" in m and "p1" in m  # both policies trained
    finally:
        trainer.stop()


def test_multi_agent_shared_policy():
    """One shared policy for all agents (parameter sharing) also trains,
    with data aggregated across multiple env runners."""
    trainer = rl.MultiAgentPPO(
        rl.CoordinationGame,
        policies={"shared": {}},
        policy_mapping_fn=lambda aid: "shared",
        num_env_runners=2,
        rollout_length=64,
        seed=0,
    )
    try:
        m = trainer.train()
        assert "shared" in m
        w = trainer.get_policy_weights("shared")
        assert "pi" in w
    finally:
        trainer.stop()


def test_appo_learns_cartpole():
    """APPO: IMPALA's async actor-learner with the PPO clipped surrogate on
    V-trace advantages must improve on CartPole."""
    algo = (
        rl.AlgorithmConfig("APPO")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(lr=2e-3, rollout_length=128, entropy_coeff=0.02, clip=0.3, seed=7)
        .build()
    )
    try:
        first_eval = algo.evaluate(3)
        for _ in range(25):
            result = algo.train()
        assert "mean_rho" in result  # rides the V-trace path
        final_eval = algo.evaluate(3)
        assert final_eval > max(first_eval * 1.5, 60.0), (first_eval, final_eval)
    finally:
        algo.stop()


def test_memory_chain_env():
    env = rl.MemoryChain(corridor=3)
    obs = env.reset(seed=0)
    cue = int(obs[:2].argmax())
    assert obs[2] == 0.0
    for _ in range(3):
        obs, r, done, _ = env.step(0)
        assert r == 0.0 and not done
        assert obs[:2].sum() == 0.0  # cue hidden in the corridor
    assert obs[2] == 1.0  # query flag
    _, r, done, _ = env.step(cue)
    assert done and r == 1.0


def test_recurrent_module_unroll_matches_steps():
    """unroll() over T steps == stepping the cell T times by hand, including
    the done-boundary state reset."""
    import jax

    m = rl.RecurrentPolicyModule(3, 2, hidden=8)
    params = m.init(jax.random.key(0))
    T, B = 5, 2
    rng = np.random.default_rng(0)
    obs = rng.normal(size=(T, B, 3)).astype(np.float32)
    dones = np.zeros((T, B), np.float32)
    dones[2, 0] = 1.0  # env 0 resets after step 2
    prev_dones = np.concatenate([np.zeros((1, B), np.float32), dones[:-1]])
    state0 = m.initial_state(B)
    logits_u, values_u, _ = m.unroll(params, obs, state0, prev_dones)
    state = state0
    for t in range(T):
        state = np.where(prev_dones[t][:, None] > 0, 0.0, state)
        lg, vl, state = m.step(params, obs[t], state)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(logits_u)[t], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(vl), np.asarray(values_u)[t], rtol=1e-5)


def test_recurrent_ppo_learns_memory_env():
    """A GRU policy must solve MemoryChain (recall the first-step cue after
    a blank corridor) — structurally impossible for the memoryless MLP,
    whose expected return is 0.  rllib counterpart: use_lstm=True on a
    stateless-obs env."""
    algo = (
        rl.AlgorithmConfig("PPO")
        .environment("MemoryChain-v0")
        .env_runners(2, num_envs_per_runner=8)
        .training(
            lr=3e-3, rollout_length=64, epochs=6, use_lstm=True,
            lstm_hidden=32, entropy_coeff=0.003, seed=1,
        )
        .build()
    )
    try:
        for _ in range(15):
            algo.train()
        final = algo.evaluate(10)
        # greedy recall accuracy: +1 right, -1 wrong; demand near-perfect
        assert final >= 0.8, final
    finally:
        algo.stop()


def test_connector_pipeline_units():
    """Connector composition + the stateful obs normalizer (rllib
    connectors / MeanStdFilter semantics)."""
    pipe = rl.ConnectorPipeline([rl.ClipObs(5.0), lambda b: b * 2.0])
    out = pipe(np.array([[10.0, -10.0, 1.0]], np.float32))
    np.testing.assert_allclose(out, [[10.0, -10.0, 2.0]])  # clip then scale
    norm = rl.RunningObsNormalizer()
    rng = np.random.default_rng(0)
    data = rng.normal(loc=5.0, scale=3.0, size=(200, 4)).astype(np.float32)
    for i in range(0, 200, 20):
        out = norm(data[i : i + 20])
    assert abs(float(out.mean())) < 0.5 and 0.5 < float(out.std()) < 2.0
    # state roundtrip: a fresh normalizer with restored state behaves identically
    st = norm.get_state()
    norm2 = rl.RunningObsNormalizer()
    norm2.set_state(st)
    probe = data[:10]
    norm.update = norm2.update = False
    np.testing.assert_allclose(norm(probe), norm2(probe), rtol=1e-6)
    # rescale actions: [-1, 1] -> [low, high]
    rs = rl.RescaleActions(0.0, 10.0)
    np.testing.assert_allclose(rs(np.array([-1.0, 0.0, 1.0])), [0.0, 5.0, 10.0])


def test_ppo_with_obs_normalizer_connector(tmp_path):
    """PPO + RunningObsNormalizer env-to-module connector learns CartPole,
    and the connector's running stats checkpoint/restore with the policy
    (a restored policy without them would see differently-scaled obs)."""
    algo = (
        rl.AlgorithmConfig("PPO")
        .environment("CartPole-v1")
        .env_runners(2, num_envs_per_runner=4)
        .training(
            lr=3e-3, rollout_length=128, epochs=6, seed=3,
            env_to_module_connector=lambda: [rl.RunningObsNormalizer()],
        )
        .build()
    )
    try:
        for _ in range(12):
            algo.train()
        final = algo.evaluate(3)
        assert final > 80.0, final
        path = algo.save(str(tmp_path / "ck"))
        st = ca.get(algo.runners[0].connector_state.remote())
        assert st is not None and st["obs"]["steps"][0]["count"] > 0
        algo.load(path)  # restores connector state to every runner
        st2 = ca.get(algo.runners[1].connector_state.remote())
        assert st2["obs"]["steps"][0]["count"] == st["obs"]["steps"][0]["count"]
        assert algo.evaluate(3) > 80.0  # restored policy still performs
    finally:
        algo.stop()
