"""The Jamba cost functions against numbers worked by hand (ISSUE 48's
sizing table) and against the program's own parameter tree."""

import os

import pytest

from benchmark.harness import cells, costs, costs_jamba as cj

FILE = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs", "jamba2_3b.json"))
CFG = FILE["model"]["kwargs"]


def test_parts_are_the_issues_arithmetic():
    assert (cj.layers(CFG, "mamba"), cj.layers(CFG, "attention")) == (26, 2)
    assert cj.d_inner(CFG) == 5120 and cj.head_dim(CFG) == 128
    # W_in 26,214,400 + W_x 983,040 + W_dt 819,200 + W_out 13,107,200
    assert cj.mamba_params(CFG) == 26214400 + 983040 + 819200 + 13107200
    # taps and bias 25,600, A_log 81,920, D and dt's bias 2 x 5,120, the
    # three inner norms 192: with the matrices ISSUE 48's 41.24 M
    assert cj.mamba_small_params(CFG) == 25600 + 81920 + 10240 + 192
    assert cj.mamba_params(CFG) + cj.mamba_small_params(CFG) == 41241792
    # W_q and W_o 6,553,600 each, W_k and W_v 327,680 each
    assert cj.attn_params(CFG) == 2 * 6553600 + 2 * 327680
    assert cj.mlp_params(CFG) == 62914560
    assert cj.table_params(CFG) == 167772160
    assert cj.kv_row_bytes(CFG) == 512     # K and V of ONE head, a layer


def test_param_count_is_the_programs_tree():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import jamba_glue
    from ray_tpu.models.jamba import JambaModel
    cfg = jamba_glue.model_config({
        "factory": "ray_tpu.models.jamba:JambaConfig", "kwargs": CFG})
    shapes = jax.eval_shape(JambaModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert cj.param_count(CFG) == sum(x.size for x in leaves)
    assert cj.weight_bytes(CFG) == sum(
        x.size * x.dtype.itemsize for x in leaves)
    # ISSUE 48: 3,029 M parameters, 6.06 GB = 5.64 GiB
    assert cj.param_count(CFG) == 3_029_337_472
    assert cj.weight_bytes(CFG) / 2 ** 30 == pytest.approx(5.647, abs=2e-3)


def test_the_files_engine_sizing_is_the_same_reckoning():
    engine = FILE["serve"]["engine"]
    assert FILE["reduced"] == [] and "reduced_from" not in FILE
    # 257 slots x 26 layers x 320 KiB of float32 state
    assert cj.mamba_state_bytes(CFG, engine["max_running"] + 1) \
        == 26 * 257 * 327680 == 2_189_557_760
    assert cj.conv_tail_bytes(CFG, engine["max_running"] + 1) \
        == 26 * 257 * 3 * 5120 * 2 == 205_271_040
    # 256 sequences x 96 pages of 16 tokens, and the null page
    assert engine["num_blocks"] == 256 * (engine["max_seq_len"] // 16) + 1
    pools = engine["num_blocks"] * 16 * cj.kv_row_bytes(CFG) * 2
    assert pools == 402_669_568
    resident = cj.weight_bytes(CFG) + 2_189_557_760 + 205_271_040 + pools
    assert resident == 8_861_267_456            # 8.253 GiB, 52% of 15.75
    for n in ("3,029,337,472", "6,063,769,088", "2,189,557,760",
              "205,271,040", "402,669,568", "8,861,267,456"):
        assert n in FILE["serve"]["engine_sizing"], n
    # every published key is in the file as published
    for key, value in CFG.items():
        if key in FILE:
            assert FILE[key] == value, key


def test_decode_step_of_256_sequences():
    peaks = cells.peaks_for("TPU v5 lite")
    step = cj.mamba_step_cost(CFG, 256)
    # 256 x 26 x 327,680 B, in and out: 4.36 GB, and 0.42 GB of operands
    assert step["bytes"] == pytest.approx(
        2 * 256 * 26 * 327680 + 26 * 4 * (256 * (3 * 5120 + 32) + 81920))
    assert step["bytes"] == pytest.approx(4.78e9, rel=5e-3)
    assert costs.roofline_least_seconds(
        step["flops"], step["bytes"], peaks)["bound"] == "memory"
    whole = cj.decode_step_cost(CFG, 256, 256 * 700)
    assert whole["bytes"] == pytest.approx(
        cj.weight_bytes(CFG) + step["bytes"]
        + 2 * cj.conv_tail_bytes(CFG, 256) + 2 * 256 * 700 * 512
        + 2 * 20 * 256 * 2 * 128 * 2)
    least = costs.roofline_least_seconds(whole["flops"], whole["bytes"],
                                         peaks)
    # 11.4 GB a step: 14.0 ms at 819 GB/s, and memory-bound; the state is
    # two fifths of it, the weights a half
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(13.97e-3, rel=0.01)
    assert 0.40 < step["bytes"] / whole["bytes"] < 0.43
    assert 0.52 < cj.weight_bytes(CFG) / whole["bytes"] < 0.54


def test_a_prefill_step_counts_what_is_required():
    one = cj.prefill_flops(CFG, 512, rows=1)
    four = cj.prefill_flops(CFG, 512, rows=4)
    # four prompts of 128 see fewer pairs than one of 512, need the head
    # three times more and move three states more
    assert four - one == pytest.approx(
        3 * 2 * cj.table_params(CFG)
        - 2 * 4 * 128 * 20 * (512 * 512 / 2) * (1 - 1 / 4), rel=1e-6)
    scan = cj.mamba_scan_cost(CFG, 512, 4)
    assert scan["flops"] == 7 * 26 * 512 * 5120 * 16
    assert scan["bytes"] == 2 * 4 * 26 * 327680 + 26 * 4 * (
        512 * (3 * 5120 + 32) + 81920)
    # the matrices dominate: 5.7 GFLOP a token
    assert one / 512 == pytest.approx(5.72e9, rel=0.01)
    assert one == pytest.approx(2 * 512 * cj.multiplied_params(CFG),
                                rel=0.02)
