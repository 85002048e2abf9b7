"""The control of the correctness check, kept as a test: the plain
reference computed in fp8 (the nearest precision below the bfloat16 that
both configurations state), put in the program's place, has to come out
as NOT correct under the limits the runners use. On the chip, at the
cells' own sizes, it did on every seed read (PERF.md section 2); here it
runs at sizes a CPU holds, and the reference in float32 put in the
program's place has to pass, so that the limits are not merely tight.

Serve runs at GPT-2 small's size: the quantisation error that reaches
the logits grows with depth and width, and at toy widths fp8 moves the
logits by less than the limit that separates it from bfloat16 at the
real width."""

import numpy as np
import pytest

from benchmark.reference import gpt2_ref
from benchmark.runners import serve_llm, train_lm

SEEDS = (1, 2, 2**31 + 7)


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_train_step_is_not_correct(seed):
    L, E, H, V, S = 2, 128, 4, 2048, 128
    p = gpt2_ref.init_params(seed, n_layer=L, n_embd=E, vocab_size=V,
                             n_positions=S)
    ids = np.random.default_rng(seed).integers(0, V, (2, S)).astype(np.int32)
    loss, gnorm = gpt2_ref.loss_and_grad_norm(p, ids, H)
    c_loss, c_gnorm = gpt2_ref.loss_and_grad_norm(p, ids, H,
                                                  quant=gpt2_ref.fp8)
    said = []
    ok, nums = train_lm.compare(
        {"loss": c_loss, "ref_loss": loss, "grad_norm": c_gnorm,
         "ref_grad_norm": gnorm}, said.append)
    assert not ok and nums["grad_rel"] > 3 * train_lm.GRAD_REL_LIMIT
    assert len(said) == 2 and all("limit" in s for s in said)
    ok, _ = train_lm.compare(
        {"loss": loss, "ref_loss": loss, "grad_norm": gnorm,
         "ref_grad_norm": gnorm}, said.append)
    assert ok


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_served_tokens_are_not_correct(seed):
    L, E, H, V, S = 12, 768, 12, 50257, 256
    p = gpt2_ref.init_params(seed, n_layer=L, n_embd=E, vocab_size=V,
                             n_positions=S)
    ids = np.random.default_rng(seed).integers(0, V, S).tolist()
    r = gpt2_ref.served_token_gaps(p, ids[:64], ids[64:], H, S,
                                   with_control=gpt2_ref.fp8)
    # what fp8 would have served in the program's place
    rows = [{"index": 0, "n": S - 64, "argmax_equal": 0,
             "logit_std": r["logit_std"],
             "max_gap": float(r["control_gaps"].max())}]
    ok, nums = serve_llm.compare(rows, lambda s: None)
    assert not ok and nums["max_gap"] > 1.5 * serve_llm.GAP_LIMIT
    # and the float32 reference's own greedy tokens pass
    greedy = gpt2_ref.served_token_gaps(p, ids[:64], ids[64:], H, S)
    assert greedy["gaps"].min() >= 0.0
    own = [{"index": 0, "n": 1, "argmax_equal": 1,
            "logit_std": r["logit_std"], "max_gap": 0.0}]
    assert serve_llm.compare(own, lambda s: None)[0]
