"""The FLOP and byte functions against numbers worked by hand."""

import pytest

from benchmark.harness import cells, costs

SMALL = dict(n_layer=12, n_embd=768, vocab_size=50257)
LARGE = dict(n_layer=36, n_embd=1280, vocab_size=50257)


def test_parameter_counts_are_the_published_ones():
    # 124,439,808 and 774,030,080: the counts of openai-community/gpt2
    # and gpt2-large (tied head counted once)
    assert costs.gpt2_param_count(**SMALL, n_positions=1024) == 124439808
    assert costs.gpt2_param_count(**LARGE, n_positions=1024) == 774030080


def test_matmul_params():
    assert costs.gpt2_matmul_params(**SMALL) == \
        12 * 12 * 768 * 768 + 50257 * 768 == 123532032
    assert costs.gpt2_matmul_params(**LARGE) == \
        36 * 12 * 1280 * 1280 + 50257 * 1280 == 772117760


def test_train_step_flops_gpt2_small_b16_s1024():
    dense = 6 * 123532032 * 16 * 1024                 # 12.14e12
    attn_fwd_layer = 2 * 16 * 1024 * 1024 * 768       # causal half
    want = dense + 3 * attn_fwd_layer * 12
    got = costs.gpt2_train_step_flops(**SMALL, batch=16, seq=1024)
    assert got == pytest.approx(want)
    assert got == pytest.approx(13.07e12, rel=2e-3)


def test_flash_kernel_cost_and_bound():
    need = costs.flash_attention_train_cost(12, 768, 16, 1024)
    assert need["flops"] == pytest.approx(3 * 2 * 16 * 1024**2 * 768 * 12)
    tensor = 16 * 1024 * 768 * 2
    assert need["bytes"] == 12 * 12 * tensor
    peaks = cells.peaks_for("TPU v5 lite")
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         peaks)
    # 0.928e12 FLOP / 197e12 = 4.71 ms against 3.6 GB / 819e9 = 4.42 ms
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(4.709e-3, rel=1e-3)


def test_decode_step_bytes_gpt2_large():
    page = costs.kv_page_bytes(36, 1280, 16)
    assert page == 36 * 2 * 16 * 1280 * 2 == 2949120
    got = costs.decode_step_bytes(
        **LARGE, n_positions=1024, param_bytes=4, live_tokens=16 * 500,
        n_seqs=16, block_size=16)
    want = 774030080 * 4 + (16 * 500 + 16 * 8) / 16 * page
    assert got == pytest.approx(want)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        cells.peaks_for("TPU v9 imaginary")
