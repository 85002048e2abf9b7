"""RLModule/Learner next-gen stack (reference: rllib/core/ —
rl_module.py, learner/learner.py, learner_group.py)."""

import numpy as np
import pytest

from ray_tpu.rllib.core import (DEFAULT_MODULE_ID, Learner, LearnerGroup,
                                MultiRLModule, PPOLearner, RLModule,
                                RLModuleSpec)
from ray_tpu.rllib.env import Box, Discrete


def _spec(seed=0):
    return RLModuleSpec(observation_space=Box(-1, 1, (4,)),
                        action_space=Discrete(2), seed=seed)


def _ppo_batch(n=64, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "obs": rng.standard_normal((n, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, (n,)).astype(np.int32),
        "action_logp": np.full((n,), -0.69, np.float32),
        "advantages": rng.standard_normal((n,)).astype(np.float32),
        "value_targets": rng.standard_normal((n,)).astype(np.float32),
    }


def test_rl_module_three_forwards():
    mod = _spec().build()
    batch = {"obs": np.zeros((8, 4), np.float32)}
    inf = mod.forward_inference(batch)
    assert inf["actions"].shape == (8,)
    assert inf["action_dist_inputs"].shape == (8, 2)
    exp = mod.forward_exploration(batch)
    assert exp["actions"].shape == (8,) and "action_logp" in exp
    # exploration on a fresh rng stream is stochastic across calls
    exp2 = mod.forward_exploration(
        {"obs": np.random.default_rng(0).standard_normal(
            (512, 4)).astype(np.float32)})
    assert len(set(exp2["actions"].tolist())) > 1
    tr = mod.forward_train(batch)
    assert set(tr) == {"action_dist_inputs", "vf_preds"}


def test_rl_module_spec_is_deterministic():
    a, b = _spec(seed=7).build(), _spec(seed=7).build()
    sa, sb = a.get_state(), b.get_state()
    import jax
    flat_a, flat_b = jax.tree.leaves(sa), jax.tree.leaves(sb)
    assert all(np.allclose(x, y) for x, y in zip(flat_a, flat_b))
    c = _spec(seed=8).build()
    assert not all(
        np.allclose(x, y) for x, y in
        zip(jax.tree.leaves(c.get_state()), flat_a))


def test_ppo_learner_update_reduces_loss():
    learner = PPOLearner(module_spec=_spec(), config={"lr": 5e-3})
    batch = _ppo_batch()
    first = learner.update_from_batch(batch)[DEFAULT_MODULE_ID]
    assert {"total_loss", "policy_loss", "vf_loss", "entropy",
            "grad_norm"} <= set(first)
    losses = [first["total_loss"]]
    for _ in range(30):
        losses.append(
            learner.update_from_batch(batch)[DEFAULT_MODULE_ID]
            ["total_loss"])
    assert losses[-1] < losses[0]


def test_multi_module_learner_updates_only_named_modules():
    learner = PPOLearner(module_specs={"a": _spec(1), "b": _spec(2)},
                         config={"lr": 1e-3})
    import jax
    b_before = jax.tree.leaves(learner.module["b"].get_state())
    out = learner.update_from_batch({"a": _ppo_batch()})
    assert set(out) == {"a"}
    b_after = jax.tree.leaves(learner.module["b"].get_state())
    assert all(np.allclose(x, y) for x, y in zip(b_before, b_after))


def test_learner_group_distributed_stays_synchronized(ray_start_shared):
    group = LearnerGroup(
        PPOLearner, num_learners=2,
        learner_kwargs={"module_spec": _spec(), "config": {"lr": 1e-3}})
    try:
        assert not group.is_local
        for i in range(3):
            group.update_from_batch(_ppo_batch(seed=i))
        # replicas applied identical averaged updates -> identical state
        import ray_tpu
        states = ray_tpu.get([w.get_state.remote()
                              for w in group._workers], timeout=240)
        import jax
        fa = jax.tree.leaves(states[0])
        fb = jax.tree.leaves(states[1])
        assert all(np.allclose(x, y, atol=1e-6) for x, y in zip(fa, fb))
    finally:
        group.shutdown()


def test_learner_group_local_mode():
    group = LearnerGroup(
        PPOLearner, num_learners=0,
        learner_kwargs={"module_spec": _spec(), "config": {"lr": 5e-3}})
    assert group.is_local
    out = group.update_from_batch(_ppo_batch())
    assert DEFAULT_MODULE_ID in out
    state = group.get_state()
    group2 = LearnerGroup(
        PPOLearner, num_learners=0,
        learner_kwargs={"module_spec": _spec(9), "config": {}})
    group2.set_state(state)
    import jax
    fa = jax.tree.leaves(state["module"])
    fb = jax.tree.leaves(group2.get_state()["module"])
    assert all(np.allclose(x, y) for x, y in zip(fa, fb))
