"""Third-wave RLlib algorithms: DDPG/TD3, Ape-X DQN, async-IMPALA.

Reference analogues: rllib/algorithms/ddpg/tests/, td3, apex_dqn/tests/,
impala/tests/test_impala.py (learner-thread behavior).
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


def test_ddpg_pendulum_smoke():
    from ray_tpu.rllib.algorithms.ddpg import DDPGConfig
    algo = (DDPGConfig().environment("Pendulum-v1")
            .rollouts(num_envs_per_worker=1, rollout_fragment_length=32)
            .training(train_batch_size=64, learning_starts=64)
            .debugging(seed=0).build())
    for _ in range(4):
        r = algo.step()
    assert r["replay_size"] >= 128
    assert "learner/critic_loss" in r
    assert "learner/actor_loss" in r  # policy_delay=1: every step
    a = algo.compute_single_action(np.zeros(3, np.float32))
    assert (-2.0 <= a).all() and (a <= 2.0).all()
    algo.cleanup()


def test_td3_twin_q_and_delay():
    from ray_tpu.rllib.algorithms.ddpg import TD3Config
    cfg = TD3Config()
    assert cfg["twin_q"] and cfg["policy_delay"] == 2
    assert cfg["smooth_target_policy"]
    algo = (TD3Config().environment("Pendulum-v1")
            .rollouts(num_envs_per_worker=1, rollout_fragment_length=32)
            .training(train_batch_size=64, learning_starts=64)
            .debugging(seed=0).build())
    policy = algo.get_policy()
    # twin critic params exist
    assert any("q2" in k for k in policy.params)
    r1 = algo.step()
    r2 = algo.step()
    # delayed actor: with policy_delay=2 the actor loss appears only on
    # even learn steps, critic loss on all
    assert "learner/critic_loss" in r2
    algo.cleanup()


def test_ddpg_learns_pendulum():
    """DDPG reaches good Pendulum reward (random policy: ~-1600; this
    config converges to ~-170 by iter 800 on CPU — threshold leaves
    seed margin). Reference shape: algorithms/ddpg/tests learning tests."""
    from ray_tpu.rllib.algorithms.ddpg import DDPGConfig
    algo = (DDPGConfig().environment("Pendulum-v1")
            .rollouts(num_envs_per_worker=4, rollout_fragment_length=16)
            .training(train_batch_size=128, learning_starts=256,
                      training_intensity=8, actor_lr=1e-3,
                      critic_lr=1e-3, exploration_noise=0.15)
            .debugging(seed=3).build())
    best = -1e9
    for i in range(700):
        r = algo.step()
        m = r["episode_reward_mean"]
        if not np.isnan(m):
            best = max(best, m)
        if best > -500:
            break
    algo.cleanup()
    assert best > -600, f"DDPG stuck at {best}"


def test_apex_dqn_cartpole(cluster):
    from ray_tpu.rllib.algorithms.apex_dqn import ApexDQNConfig
    algo = (ApexDQNConfig().environment("CartPole-v1")
            .rollouts(num_workers=2, num_envs_per_worker=2,
                      rollout_fragment_length=16)
            .training(train_batch_size=32, learning_starts=200,
                      replay_buffer_capacity=5000,
                      train_intensity_per_iter=2)
            .debugging(seed=0).build())
    total_learned = 0
    for _ in range(10):
        r = algo.step()
        total_learned = r["num_learner_steps"]
    assert r["replay_size"] >= 200
    assert total_learned > 0, "learner never consumed replay samples"
    assert r["num_env_steps_sampled_this_iter"] > 0
    # per-worker epsilon ladder: first worker explores least
    eps = ray_tpu.get([
        w.apply.remote(lambda w: w.policy.exploration_epsilon)
        for w in algo.workers.remote_workers], timeout=240)
    assert eps[0] > eps[1] or np.isclose(eps[0], 0.4), eps
    assert algo.workers.local_worker.policy.exploration_epsilon == 0.0
    algo.cleanup()


def test_impala_async_learner_overlap(cluster):
    """The learner thread consumes batches while samplers stay in
    flight — the defining IMPALA decoupling."""
    from ray_tpu.rllib.algorithms.impala import IMPALAConfig
    algo = (IMPALAConfig().environment("CartPole-v1")
            .rollouts(num_workers=2, num_envs_per_worker=2,
                      rollout_fragment_length=32)
            .training(max_sample_batches_per_iter=6)
            .debugging(seed=0).build())
    assert algo._learner is not None and algo._learner.is_alive()
    for _ in range(4):
        r = algo.step()
    # learner thread processed batches asynchronously
    assert r["learner/num_learner_steps"] > 0
    assert r["learner/num_samples_trained"] > 0
    # samplers were relaunched while learning happened
    assert len(algo._in_flight) > 0
    assert "learner/policy_loss" in r
    algo.cleanup()
    assert algo._learner.stopped


def test_impala_async_matches_sync_learning(cluster):
    """Async IMPALA still learns CartPole (correctness of the decoupled
    path, not just liveness)."""
    from ray_tpu.rllib.algorithms.impala import IMPALAConfig
    algo = (IMPALAConfig().environment("CartPole-v1")
            .rollouts(num_workers=2, num_envs_per_worker=4,
                      rollout_fragment_length=32)
            .training(lr=3e-3, entropy_coeff=0.005,
                      max_sample_batches_per_iter=4)
            .debugging(seed=1).build())
    best = 0.0
    for _ in range(30):
        r = algo.step()
        if not np.isnan(r["episode_reward_mean"]):
            best = max(best, r["episode_reward_mean"])
        if best > 60:
            break
    algo.cleanup()
    assert best > 60, f"async IMPALA stuck at {best}"


def test_appo_learns_cartpole(cluster):
    """APPO: IMPALA's async machinery with PPO's clipped surrogate."""
    from ray_tpu.rllib.algorithms.appo import APPOConfig
    algo = (APPOConfig().environment("CartPole-v1")
            .rollouts(num_workers=2, num_envs_per_worker=4,
                      rollout_fragment_length=32)
            .training(lr=3e-3, entropy_coeff=0.005,
                      max_sample_batches_per_iter=4)
            .debugging(seed=0).build())
    assert algo._learner is not None  # async learner thread active
    best = 0.0
    # 70 iters: the async learner's sample/update interleaving is
    # timing-dependent under 1-core suite contention — 45 was observed
    # to land at 54-58 under a concurrently running full suite; the
    # early break keeps converged runs at ~12-30 iters
    for _ in range(70):
        r = algo.step()
        if not np.isnan(r["episode_reward_mean"]):
            best = max(best, r["episode_reward_mean"])
        if best > 60:
            break
    algo.cleanup()
    assert best > 60, f"APPO stuck at {best}"
