"""The LFM2 cost functions against numbers worked by hand (ISSUE 53's
sizing table), against the program's own parameter tree, and against a
brute-force count over every layer."""

import os

import pytest

from benchmark.harness import cells, costs, costs_lfm2 as cl

FILE = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs", "lfm2_8b_a1b.json"))
CFG = FILE["model"]["kwargs"]


def test_parts_are_the_issues_arithmetic():
    assert (cl.layers(CFG, "conv"), cl.layers(CFG, "full_attention")) \
        == (12, 4)
    assert (cl.dense_layers(CFG), cl.routed_layers(CFG)) == (2, 14)
    assert cl.head_dim(CFG) == 64
    # W_in 12,582,912 + W_out 4,194,304, and the taps 6,144
    assert cl.conv_params(CFG) == 12582912 + 4194304
    assert cl.conv_taps(CFG) == 6144
    # W_q and W_o 4,194,304 each, W_k and W_v 1,048,576 each
    assert cl.attn_params(CFG) == 2 * 4194304 + 2 * 1048576
    assert cl.mlp_params(CFG) == 44040192
    assert cl.expert_params(CFG) == 11010048        # 22.0 MB
    assert cl.router_params(CFG) == 65536 + 32
    assert cl.table_params(CFG) == 134217728
    assert cl.kv_row_bytes(CFG) == 2048     # K and V of 8 heads of 64
    # a cached token over the stage: 8 KiB
    assert cl.layers(CFG, "full_attention") * cl.kv_row_bytes(CFG) == 8192


def _program_shapes(**changed):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import lfm2_glue
    from ray_tpu.models.lfm2 import Lfm2Model
    cfg = lfm2_glue.model_config({
        "factory": "ray_tpu.models.lfm2:Lfm2Config",
        "kwargs": dict(CFG, **changed)})
    shapes = jax.eval_shape(Lfm2Model(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return jax.tree_util.tree_leaves(shapes)


@pytest.mark.parametrize("layers", [16, 24, 3])
def test_param_count_is_the_programs_tree(layers):
    leaves = _program_shapes(num_hidden_layers=layers)
    c = dict(CFG, num_hidden_layers=layers)
    assert cl.param_count(c) == sum(x.size for x in leaves)
    assert cl.weight_bytes(c) == sum(
        x.size * x.dtype.itemsize for x in leaves)
    if layers == 16:    # ISSUE 53: 5,399.1 M parameters, 10.80 GB
        assert cl.param_count(c) == 5_399_129_024
        assert cl.weight_bytes(c) == 10_800_230_144
        assert cl.weight_bytes(c) / 2 ** 30 == pytest.approx(10.058,
                                                             abs=2e-3)
    if layers == 24:    # the published 8.3 B, with the head tied
        assert cl.param_count(c) == 8_339_930_560


def test_the_counts_are_a_brute_force_walk_over_the_layers():
    """Layer by layer, as the published lists read: what every token is
    multiplied by outside the experts, and what is stored."""
    D, V = CFG["hidden_size"], CFG["vocab_size"]
    d = cl.head_dim(CFG)
    multiplied = stored = 0
    for i, op in enumerate(CFG["layer_types"][:CFG["num_hidden_layers"]]):
        if op == "conv":
            mixer, small = D * 3 * D + D * D, CFG["conv_L_cache"] * D
        else:
            mixer = D * CFG["num_attention_heads"] * d * 2 \
                + D * CFG["num_key_value_heads"] * d * 2
            small = 2 * d
        if i < CFG["num_dense_layers"]:
            ffn, experts = 3 * D * CFG["intermediate_size"], 0
        else:
            ffn = D * CFG["num_experts"]
            experts = CFG["num_experts"] * 3 * D \
                * CFG["moe_intermediate_size"] + CFG["num_experts"]
        multiplied += mixer + ffn
        stored += mixer + small + ffn + experts + 2 * D
    assert cl.multiplied_params(CFG) == multiplied
    assert cl.param_count(CFG) == stored + V * D + D


def test_the_files_engine_sizing_is_the_same_reckoning():
    engine = FILE["serve"]["engine"]
    assert FILE["reduced"] == ["num_hidden_layers"]
    assert FILE["reduced_from"] == {"num_hidden_layers": 24}
    assert cl.conv_tail_bytes(CFG, engine["max_running"] + 1) \
        == 12 * 257 * 2 * 2048 * 2 == 25_264_128
    assert engine["num_blocks"] == 256 * 72 + 1
    pools = engine["num_blocks"] * 16 * cl.kv_row_bytes(CFG) * 4
    assert pools == 2_416_050_176
    resident = cl.weight_bytes(CFG) + 25_264_128 + pools
    assert resident == 13_241_544_448          # 12.332 GiB, 78% of 15.75
    for n in ("5,399,129,024", "10,800,230,144", "2,416,050,176",
              "25,264,128", "13,241,544,448"):
        assert n in FILE["serve"]["engine_sizing"], n
    # every published key is in the file as published, but the depth
    for key, value in CFG.items():
        if key in FILE and key != "num_hidden_layers":
            assert FILE[key] == value, key
    assert FILE["num_hidden_layers"] == CFG["num_hidden_layers"] == 16
    assert len(FILE["layer_types"]) == 24


def test_the_page_pool_covers_the_multiset_by_four_deviations():
    import numpy as np

    from benchmark.harness import loadgen
    traffic = cells.load_json(os.path.join(
        cells.BENCH_DIR, "traffic", "serve_closed256_1k.json"))
    pool = loadgen.length_pool(traffic)
    assert len(pool) == 768
    blocks = np.asarray([-(-(p + o) // 16) for p, o in pool])
    need = 256 * blocks.mean() + 4 * 16 * blocks.std()
    assert need == pytest.approx(17413, abs=2)
    assert need < FILE["serve"]["engine"]["num_blocks"]
    assert max(p + o for p, o in pool) <= traffic["max_total_len"] \
        == FILE["serve"]["engine"]["max_seq_len"]


def test_decode_step_of_256_sequences():
    peaks = cells.peaks_for("TPU v5 lite")
    # every expert of every routed layer touched, 4 pairs a row a layer
    touched, pairs = 14 * 32, 14 * 256 * 4
    moe = cl.moe_experts_cost(CFG, touched, pairs)
    assert moe["bytes"] == 448 * 11010048 * 2 + 14336 * 2 * 2048 * 2
    assert moe["flops"] == 2 * 14336 * 11010048         # 0.32 TFLOP
    # ... where the touched form multiplies 8 times the rows: as ROUTED
    assert moe["flops"] == pytest.approx(0.3157e12, rel=1e-3)
    least = costs.roofline_least_seconds(moe["flops"], moe["bytes"], peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(12.19e-3, rel=0.01)
    whole = cl.decode_step_cost(CFG, 256, 256 * 700, touched, pairs)
    assert whole["bytes"] == pytest.approx(
        cl.weight_bytes(CFG) + 14336 * 2 * 2048 * 2
        + 2 * cl.conv_tail_bytes(CFG, 256) + 256 * 700 * 8192
        + 4 * 32 * 256 * 2 * 64 * 2)
    least = costs.roofline_least_seconds(whole["flops"], whole["bytes"],
                                         peaks)
    # 12.4 GB a step: 15.2 ms at 819 GB/s; the experts are four fifths
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(15.2e-3, rel=0.01)
    assert 0.79 < moe["bytes"] / whole["bytes"] < 0.81
    # half the experts touched: their weights fall out of the bytes
    half = cl.decode_step_cost(CFG, 256, 256 * 700, touched // 2, pairs)
    assert whole["bytes"] - half["bytes"] == 224 * 11010048 * 2


def test_the_convolution_and_the_attention_of_a_decode_step():
    conv = cl.conv_cost(CFG, 256, 256)
    # 12 layers: 33.6 MB of matrices and taps each, the rows' tails in
    # and out, a row's input and output
    assert conv["bytes"] == 12 * (16777216 + 6144) * 2 \
        + 2 * 12 * 256 * 2 * 2048 * 2 + 12 * 256 * 2 * 2048 * 2
    assert conv["flops"] == 12 * 256 * (2 * 16777216 + 8 * 2048)
    attend = cl.attend_cost(CFG, 256, 256 * 700)
    assert attend["bytes"] == 4 * 256 * 700 * 2048 + 4 * 32 * 256 * 2 * 64 * 2
    assert attend["flops"] == 4.0 * 64 * 4 * 32 * 256 * 700


def test_a_prefill_step_counts_what_is_required():
    one = cl.prefill_flops(CFG, 1024, rows=1)
    four = cl.prefill_flops(CFG, 1024, rows=4)
    # four prompts of 256 see fewer pairs than one of 1,024 and need the
    # head three times more
    assert four - one == pytest.approx(
        3 * 2 * cl.table_params(CFG)
        - 4 * 4 * 64 * 32 * (1024 * 1024 / 2) * (1 - 1 / 4), rel=1e-6)
    # 1.91 GFLOP a token with the head once a prompt (ISSUE 53's 2.2
    # counts the head, 0.27, a token), the routed pairs 64% of it
    assert one / 1024 == pytest.approx(1.915e9, rel=0.01)
    assert one / 1024 + 2 * cl.table_params(CFG) == pytest.approx(2.18e9,
                                                                  rel=0.01)
    routed = 2 * 14 * 4 * cl.expert_params(CFG)
    assert 0.6 < routed / (one / 1024) < 0.7
