"""The paged decode attention's cost function against numbers worked by
hand at gpt2_large.serve_closed32's shapes."""

import os

import pytest

from benchmark.harness import cells, costs, costs_paged_attention as cp

KW = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs", "gpt2_large.json"))["model"]


def test_sixteen_rows_of_four_hundred_tokens():
    k = KW["kwargs"]
    need = cp.paged_attention_decode_cost(
        k["n_layer"], k["n_embd"], 16 * 400, 16, KW["kv_bytes"])
    # a token's K and V of one layer: 2 x 1280 x 2 B = 5,120 B; 6,400
    # tokens x 36 layers = 1.18 GB; q and the output 16 x 5,120 B a layer
    assert need["bytes"] == 36 * (6400 * 5120 + 16 * 5120) == 1182597120
    assert need["flops"] == 4 * 6400 * 1280 * 36
    least = costs.roofline_least_seconds(
        need["flops"], need["bytes"], cells.peaks_for("TPU v5 lite"))
    # 1.18 GB at 819 GB/s: 1.44 ms a step, and the bytes bound it
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(1.444e-3, rel=1e-3)


def test_grows_with_the_live_tokens_and_not_with_the_padding():
    a = cp.paged_attention_decode_cost(36, 1280, 6400, 16)
    b = cp.paged_attention_decode_cost(36, 1280, 12800, 16)
    assert b["flops"] == 2 * a["flops"]
    assert b["bytes"] - a["bytes"] == 36 * 6400 * 5120
    # the whole padded context of 16 rows would be 2.56x these tokens
    padded = cp.paged_attention_decode_cost(36, 1280, 16 * 1024, 16)
    assert padded["bytes"] / a["bytes"] == pytest.approx(2.56, rel=1e-2)
