"""The Kimi-Linear cost functions against numbers worked by hand (ISSUE
28's sizing table) and against the program's own parameter tree."""

import os

import pytest

from benchmark.harness import cells, costs, costs_kimi_linear as ck

CFG = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs", "kimi_linear_48b_a3b.json"))["model"]["kwargs"]


def test_parts_are_the_issues_arithmetic():
    assert ck.layer_kinds(CFG) == ["kda"] * 3 + ["mla"] + ["kda"] * 3 \
        + ["mla"]
    # 3 x 2304x4096 + 4096x2304 + 2 gates (2304->128->4096) + 2304x32
    assert ck.kda_mixer_params(CFG) == 4 * 2304 * 4096 \
        + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 == 39460864
    # 2304x6144 + 2304x576 + 512x8192 + 4096x2304
    assert ck.mla_mixer_params(CFG) == 29114368
    assert ck.expert_params(CFG) == 3 * 2304 * 1024 == 7077888
    assert ck.dense_ffn_params(CFG) == 63700992
    assert ck.routed_layers(CFG) == 7


def test_param_count_is_the_programs_tree():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kimi_linear_glue
    from ray_tpu.models.kimi_linear import KimiLinearModel
    cfg = kimi_linear_glue.model_config({
        "factory": "ray_tpu.models.kimi_linear:KimiLinearConfig",
        "kwargs": CFG})
    shapes = jax.eval_shape(KimiLinearModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    matrices = sum(x.size for x in jax.tree_util.tree_leaves(shapes)
                   if x.ndim >= 2 and x.shape[0] > 4)
    assert ck.param_count(CFG) == matrices
    assert ck.param_count(CFG) == pytest.approx(3.772e9, rel=1e-3)


def test_decode_step_of_64_sequences():
    # state: 6 layers x 64 x 32 x 128 x 128 x 4 B = 805 MB, in and out
    assert ck.kda_state_bytes(CFG, 64) == 6 * 64 * 32 * 128 * 128 * 4
    rec = ck.kda_recurrence_cost(CFG, 64)
    assert rec["bytes"] == pytest.approx(1.61e9, rel=1e-2)
    peaks = cells.peaks_for("TPU v5 lite")
    assert costs.roofline_least_seconds(
        rec["flops"], rec["bytes"], peaks)["bound"] == "memory"
    # 390 of 448 (expert, layer) pairs touched, 128 assignments a layer
    moe = ck.moe_experts_cost(CFG, 390, 7 * 128)
    assert moe["bytes"] == pytest.approx(390 * 7077888 * 2, rel=2e-3)
    assert moe["flops"] == 2 * 7 * 128 * 7077888
    step = ck.decode_step_cost(CFG, 64, 64 * 2000, 390, 7 * 128)
    always = ck.always_read_params(CFG)
    assert always == 6 * 39460864 + 2 * 29114368 + 63700992 \
        + 7 * (2304 * 256 + 7077888) + 2304 * 40960
    assert step["bytes"] == pytest.approx(
        always * 2 + moe["bytes"] + rec["bytes"]
        + 2 * 64 * 2000 * 576 * 2)
    least = costs.roofline_least_seconds(step["flops"], step["bytes"],
                                         peaks)
    # ~8.2 GB a step: 10 ms at 819 GB/s, and memory-bound
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(10.0e-3, rel=0.1)
