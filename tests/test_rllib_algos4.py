"""Fourth-wave RLlib algorithms: SimpleQ, A3C, DDPPO, Ape-X DDPG,
CQL, CRR, ES, ARS, LinUCB/LinTS bandits.

Reference analogues: rllib/algorithms/{simple_q,a3c,ddppo,apex_ddpg,
cql,crr,es,ars,bandit}/tests/.
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                       object_store_memory=256 * 1024 * 1024)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def pendulum_dataset(tmp_path_factory):
    """Offline Pendulum data from a noisy PD controller (mean return
    ≈ -950 vs random ≈ -1270) — good enough for CQL/CRR to beat
    random by imitating-and-improving."""
    from ray_tpu.rllib.env import PendulumEnv
    from ray_tpu.rllib.offline import JsonWriter
    from ray_tpu.rllib.sample_batch import SampleBatch
    d = str(tmp_path_factory.mktemp("pendulum_offline"))
    rng = np.random.default_rng(0)
    env = PendulumEnv({"seed": 0})
    obs_l, act_l, rew_l, done_l, nobs_l = [], [], [], [], []
    for ep in range(30):
        obs, _ = env.reset(seed=ep)
        for _ in range(200):
            cos_th, sin_th, thdot = obs
            th = np.arctan2(sin_th, cos_th)
            a = np.clip(-8.0 * th - 2.0 * thdot
                        + rng.normal(0, 0.4), -2, 2)
            nobs, r, term, trunc, _ = env.step(
                np.array([a], np.float32))
            obs_l.append(obs); act_l.append([a]); rew_l.append(r)
            done_l.append(term or trunc); nobs_l.append(nobs)
            obs = nobs
            if term or trunc:
                break
    from ray_tpu.rllib.sample_batch import SampleBatch as SB
    w = JsonWriter(d)
    w.write(SB({
        SB.OBS: np.asarray(obs_l, np.float32),
        SB.ACTIONS: np.asarray(act_l, np.float32),
        SB.REWARDS: np.asarray(rew_l, np.float32),
        SB.DONES: np.asarray(done_l, bool),
        SB.NEXT_OBS: np.asarray(nobs_l, np.float32),
    }))
    w.close()
    return d


def test_simple_q_has_no_extras_and_learns_smoke():
    from ray_tpu.rllib.algorithms.simple_q import SimpleQConfig
    cfg = SimpleQConfig()
    assert cfg["double_q"] is False and not cfg["prioritized_replay"]
    algo = (SimpleQConfig().environment("CartPole-v1")
            .rollouts(rollout_fragment_length=32)
            .training(train_batch_size=32, learning_starts=64,
                      num_steps_sampled_before_learning=64)
            .debugging(seed=0).build())
    for _ in range(4):
        r = algo.step()
    assert "learner/mean_q" in r
    algo.cleanup()


def test_a3c_async_grads(cluster):
    from ray_tpu.rllib.algorithms.a3c import A3CConfig
    algo = (A3CConfig().environment("CartPole-v1")
            .rollouts(num_workers=2, rollout_fragment_length=50)
            .debugging(seed=0).build())
    total_grads = 0
    for _ in range(4):
        r = algo.step()
        total_grads += r["num_grads_applied"]
    assert total_grads >= 4
    assert "learner/policy_loss" in r
    assert r["num_env_steps_sampled_this_iter"] > 0
    algo.cleanup()


def test_a3c_requires_workers():
    from ray_tpu.rllib.algorithms.a3c import A3CConfig
    with pytest.raises(ValueError, match="num_workers"):
        (A3CConfig().environment("CartPole-v1")
         .rollouts(num_workers=0).build())


def test_ddppo_decentralized_learning(cluster):
    from ray_tpu.rllib.algorithms.ddppo import DDPPOConfig
    algo = (DDPPOConfig().environment("CartPole-v1")
            .rollouts(num_workers=2, rollout_fragment_length=100)
            .training(num_sgd_iter=3, sgd_minibatch_size=64)
            .debugging(seed=0).build())
    r = algo.step()
    assert r["num_ddppo_workers"] == 2
    assert r["num_env_steps_sampled_this_iter"] >= 200
    # driver policy got the averaged weights (it never learned itself)
    lw_w = algo.workers.local_worker.policy.get_weights()
    rw_w = ray_tpu.get(
        algo.workers.remote_workers[0].get_weights.remote(), timeout=240)
    flat_l = np.concatenate([np.ravel(x) for x in
                             _tree_leaves(lw_w)])
    flat_r = np.concatenate([np.ravel(x) for x in
                             _tree_leaves(rw_w)])
    np.testing.assert_allclose(flat_l, flat_r, rtol=1e-5)
    algo.cleanup()


def _tree_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def test_apex_ddpg_noise_ladder_and_learning(cluster):
    from ray_tpu.rllib.algorithms.apex_ddpg import ApexDDPGConfig
    algo = (ApexDDPGConfig().environment("Pendulum-v1")
            .rollouts(num_workers=2, rollout_fragment_length=16)
            .training(train_batch_size=64, learning_starts=128)
            .debugging(seed=0).build())
    for _ in range(6):
        r = algo.step()
    assert r["replay_size"] >= 128
    assert r["num_learner_steps"] > 0
    assert "learner/critic_loss" in r
    # per-worker noise ladder: EARLIER workers explore more
    # (base^1 > base^8 for base < 1)
    noises = ray_tpu.get([
        w.apply.remote(lambda w: w.policy.exploration_noise)
        for w in algo.workers.remote_workers], timeout=240)
    assert noises[0] > noises[1]
    assert algo.workers.local_worker.policy.exploration_noise == 0.0
    algo.cleanup()


def test_cql_offline_learns(pendulum_dataset):
    from ray_tpu.rllib.algorithms.cql import CQLConfig
    algo = (CQLConfig().environment("Pendulum-v1")
            .offline_data(input_path=pendulum_dataset)
            .training(train_batch_size=128, num_iters_per_step=30,
                      bc_iters=150, cql_alpha=0.5, lr=1e-3)
            .debugging(seed=0).build())
    ev0 = algo.evaluate(num_episodes=5)["evaluation"][
        "episode_reward_mean"]
    for _ in range(25):
        r = algo.step()
    assert "learner/cql_penalty" in r
    assert np.isfinite(r["learner/critic_loss"])
    # offline training improves on the untrained policy (fully seeded:
    # measured -1367 → -1264 after 750 learn steps; margin for drift)
    ev1 = algo.evaluate(num_episodes=5)["evaluation"][
        "episode_reward_mean"]
    assert ev1 > ev0 + 30, (ev0, ev1)
    algo.cleanup()


def test_crr_binary_and_exp_weights(pendulum_dataset):
    from ray_tpu.rllib.algorithms.crr import CRRConfig
    algo = (CRRConfig().environment("Pendulum-v1")
            .offline_data(input_path=pendulum_dataset)
            .training(train_batch_size=128, num_iters_per_step=10,
                      weight_type="binary")
            .debugging(seed=0).build())
    r = algo.step()
    assert 0.0 <= r["learner/mean_weight"] <= 1.0
    algo.cleanup()
    algo = (CRRConfig().environment("Pendulum-v1")
            .offline_data(input_path=pendulum_dataset)
            .training(train_batch_size=128, num_iters_per_step=10,
                      weight_type="exp", temperature=1.0)
            .debugging(seed=0).build())
    r = algo.step()
    assert r["learner/mean_weight"] > 0.0
    assert np.isfinite(r["learner/actor_loss"])
    algo.cleanup()


def test_es_learns_cartpole(cluster):
    """ES improves CartPole reward well above random (~20)."""
    from ray_tpu.rllib.algorithms.es import ESConfig
    algo = (ESConfig().environment("CartPole-v1")
            .rollouts(num_workers=2)
            .training(rollouts_per_worker=10, sigma=0.1, stepsize=0.05,
                      episode_horizon=200, noise_table_size=500_000)
            .debugging(seed=0).build())
    best = 0.0
    for i in range(15):
        r = algo.step()
        best = max(best, r["perturbation_reward_mean"])
        if best > 80:
            break
    algo.cleanup()
    assert best > 60, f"ES stuck at {best}"


def test_ars_top_directions(cluster):
    from ray_tpu.rllib.algorithms.es import ARSConfig
    algo = (ARSConfig().environment("CartPole-v1")
            .rollouts(num_workers=2)
            .training(rollouts_per_worker=6, num_top_directions=4,
                      sigma=0.1, stepsize=0.05, episode_horizon=100,
                      noise_table_size=500_000)
            .debugging(seed=0).build())
    r1 = algo.step()
    r2 = algo.step()
    assert np.isfinite(r2["update_gnorm"]) and r2["update_gnorm"] > 0
    assert r2["episodes_this_iter"] == 24  # 2 workers * 6 pairs * 2
    algo.cleanup()


def test_bandit_linucb_low_regret():
    from ray_tpu.rllib.algorithms.bandit import (
        BanditLinUCBConfig, LinearDiscreteBanditEnv)
    algo = (BanditLinUCBConfig()
            .environment(LinearDiscreteBanditEnv,
                         env_config={"feature_dim": 4, "num_arms": 3,
                                     "payoff_seed": 7})
            .debugging(seed=0).build())
    rewards = [algo.step()["learner/mean_reward"] for _ in range(15)]
    # converged per-step reward should be clearly positive (optimal arm
    # mean ≈ 1.0 for this payoff seed; uniform-random ≈ 0)
    assert np.mean(rewards[-5:]) > 0.5, rewards
    algo.cleanup()


def test_bandit_lints_converges():
    from ray_tpu.rllib.algorithms.bandit import (
        BanditLinTSConfig, LinearDiscreteBanditEnv)
    algo = (BanditLinTSConfig()
            .environment(LinearDiscreteBanditEnv,
                         env_config={"feature_dim": 4, "num_arms": 3,
                                     "payoff_seed": 7})
            .debugging(seed=0).build())
    rewards = [algo.step()["learner/mean_reward"] for _ in range(15)]
    assert np.mean(rewards[-5:]) > 0.5, rewards
    # checkpoint roundtrip keeps the sufficient statistics
    state = algo.save_checkpoint()
    A_before = algo.get_policy().A.copy()
    algo.load_checkpoint(state)
    np.testing.assert_allclose(algo.get_policy().A, A_before)
    algo.cleanup()


def test_algorithms_registry_exports():
    """All 26 algorithm classes import from the package root."""
    from ray_tpu.rllib import algorithms as A
    for name in ["PPO", "DDPPO", "APPO", "IMPALA", "DQN", "SimpleQ",
                 "ApexDQN", "ApexDDPG", "R2D2", "PG", "A2C", "A3C",
                 "SAC", "DDPG", "TD3", "BC", "MARWIL", "CQL", "CRR",
                 "DT", "ES", "ARS", "QMix", "MADDPG", "BanditLinUCB",
                 "BanditLinTS"]:
        assert hasattr(A, name), name
        assert hasattr(A, name + "Config"), name
