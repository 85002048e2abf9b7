"""The controls of the Laguna cell's correctness check, kept as a test:
put in the program's place, each has to come out as NOT correct through
the runner's ``compare``, by at least one of its limits:

- the plain reference computed in fp8 matrix products (the nearest
  precision below the bfloat16 that the configuration states for weights,
  activations and cached rows): by the cached K and V rows' error in
  both page groups;
- the plain reference with the sliding layers seeing the whole context
  (a window that is not applied): by the rows of every layer behind the
  first sliding one, the ring's among them.

The float32 reference's own greedy tokens and rows have to pass, so that
the limits are not merely tight.

Sizes: the published widths (hidden 2048, 48 / 64 heads of 128 over 8
key/value heads, the window of 512, YaRN and the partial rotary as
published, dense width 8192, expert width 512, 8 experts a token), the
cell's own layers 0-4 (the second sliding layer's rows are the first to
see the first one's window), 32 experts, 2,048 rows of the vocabulary,
one request of 64 + 1,472 tokens (three windows): what a CPU holds. The
seeds are not picked."""

import numpy as np
import pytest

from benchmark.reference import laguna_glue as glue
from benchmark.reference import laguna_ref as ref
from benchmark.runners import serve_llm_laguna as runner

SIZES = dict(vocab_size=2048, num_hidden_layers=5, num_experts=32,
             max_seq_len=1536)
N_PROMPT, N = 64, 1536


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_the_controls_in_the_programs_place_are_not_correct(seed, capsys):
    from ray_tpu.models.laguna import LagunaConfig
    cfg = LagunaConfig(**SIZES)
    params = glue.init_for(cfg, seed)["params"]
    sizes = ref.sizes_of(cfg)
    ids = np.random.default_rng(seed).integers(0, 2048, N).tolist()
    r = ref.served_token_gaps(params, ids[:N_PROMPT], ids[N_PROMPT:], sizes,
                              N, 16, controls=ref.CONTROLS)
    assert r["gaps"].min() >= 0.0
    written = r["ring_written"]
    # 1,535 tokens fed: the ring's 33 pages hold positions 1008 .. 1534
    assert written.sum() == (N - 1) - ((N - 2) // 16 - 32) * 16 == 527
    base = {"index": 0, "n": N - N_PROMPT, "argmax_equal": 0,
            "logit_std": r["logit_std"], "cache_tokens_ok": True,
            "ring_rows": int(written.sum())}
    rows = {"float32": dict(base, max_gap=0.0, argmax_equal=N - N_PROMPT,
                            full_err=0.0, ring_err=0.0)}
    for name in ref.CONTROLS:
        rows[name] = dict(
            base, max_gap=float(r[f"control_{name}_gaps"].max()),
            full_err=runner._rel_err(r[f"control_{name}_full"], r["full"]),
            ring_err=runner._rel_err(
                r[f"control_{name}_ring"][:, :, written],
                r["ring"][:, :, written]))
    said, verdict = [], {}
    for name, row in rows.items():
        verdict[name], nums = runner.compare([row], said.append)
        with capsys.disabled():
            print(f"\n[control] seed {seed}, {name} in the program's "
                  f"place: correct={verdict[name]} " + ", ".join(
                      f"{k} {v:.5f}" for k, v in nums.items()))
    assert all("limit" in line for line in said)
    assert verdict == {"float32": True, "fp8": False, "whole_context": False}
    assert rows["fp8"]["full_err"] > runner.KV_ERR_LIMIT
    assert rows["fp8"]["ring_err"] > runner.KV_ERR_LIMIT
    assert rows["whole_context"]["ring_err"] > runner.KV_ERR_LIMIT
    # rows kept in 8 bits: their rounding alone
    rounding = runner._rel_err(ref.fp8(r["full"]), r["full"])
    with capsys.disabled():
        print(f"[control] rows rounded to 8 bits read {rounding:.5f}")
    assert rounding > 0.02
