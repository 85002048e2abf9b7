"""MAML (meta-gradients) and SlateQ (slate Q-decomposition). Reference
analogues: rllib/algorithms/{maml,slateq}/. A learning test each with an
explicit threshold, and SlateQ's decomposition invariant. The other
model-based families are in test_rllib_modelbased.py.
"""

import numpy as np


def test_maml_adaptation_gap():
    from ray_tpu.rllib.algorithms.maml import MAMLConfig
    algo = (MAMLConfig().training(inner_lr=0.3, lr=3e-3)
            .debugging(seed=0).build())
    before = algo.adaptation_eval(8)
    for _ in range(20):
        r = algo.step()
    assert np.isfinite(r["learner/meta_loss"])
    after = algo.adaptation_eval(8)
    # one inner step on a held-out task must pay off (the MAML claim)
    gap = after["post_adaptation_reward"] - after["pre_adaptation_reward"]
    assert gap > 2.0, after
    # and meta-training must have improved the post-adaptation policy
    assert after["post_adaptation_reward"] > \
        before["post_adaptation_reward"] + 2.0, (before, after)


def test_slateq_beats_random_slates():
    from ray_tpu.rllib.algorithms.slateq import SlateQConfig
    algo = SlateQConfig().debugging(seed=0).build()
    baseline = algo.random_baseline(30)
    for _ in range(30):
        r = algo.step()
    assert np.isfinite(r["learner/loss"])
    trained = algo.evaluate(20)["evaluation"]["episode_reward_mean"]
    assert trained > baseline + 1.5, (baseline, trained)
    st = algo.save_checkpoint()
    algo.load_checkpoint(st)


def test_slateq_decomposition_matches_choice_model():
    """Q(s, A) must decompose through the SAME MNL probabilities the
    simulator uses — pin the slate-building rule to the env's choice
    scores."""
    from ray_tpu.rllib.algorithms.slateq import (InterestEvolutionEnv,
                                                 SlateQConfig)
    env = InterestEvolutionEnv({"num_docs": 8, "slate_size": 2})
    obs, _ = env.reset(seed=0)
    v = env.choice_scores(obs)
    assert v.shape == (8,) and (v > 0).all()
    algo = SlateQConfig().environment(
        "interest_evolution",
        env_config={"num_docs": 8, "slate_size": 2}).debugging(
        seed=0).build()
    q = np.arange(8, dtype=np.float32)
    slate = algo._build_slate(q, obs)
    v_all = algo.env.choice_scores(obs)
    want = np.argsort(-(v_all * q))[:2]
    assert list(slate) == list(want)
