"""The controls of the SmallThinker cell's correctness check, kept as a
test: put in the program's place, each has to come out as NOT correct
through the runner's ``compare``, by at least one of its limits:

- the plain reference computed in fp8 matrix products (the nearest
  precision below the bfloat16 that the configuration states for weights,
  activations and cached rows): by the cached K and V rows' error in
  both page groups;
- the plain reference with the window layers seeing the whole context
  (a window that is not applied): by the rows of every layer behind the
  first window layer, the ring's among them.

The float32 reference's own greedy tokens and rows have to pass, so that
the limits are not merely tight.

Sizes: the published widths (hidden 2560, 28 heads of 128 over 4
key/value heads, rotary at theta 1.5e6 on the window layers and none on
the full ones, expert width 768, 6 experts a token), layers 0-4 of the
published layout (the second window layer's rows are the first to see
the first one's window; layer 4 is the second full layer), 16 experts,
2,048 rows of the vocabulary, and a window CUT TO 512 so that one
request of 64 + 1,472 tokens runs three windows long: what a CPU holds.
The limits themselves were set from the chip's readings at the published
window (PERF.md section 2). The seeds are not picked."""

import numpy as np
import pytest

from benchmark.reference import smallthinker_glue as glue
from benchmark.reference import smallthinker_ref as ref
from benchmark.runners import serve_llm_smallthinker as runner

SIZES = dict(vocab_size=2048, num_hidden_layers=5,
             moe_num_primary_experts=16, sliding_window_size=512,
             max_seq_len=1536)
N_PROMPT, N = 64, 1536


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_the_controls_in_the_programs_place_are_not_correct(seed, capsys):
    from ray_tpu.models.smallthinker import SmallThinkerConfig
    cfg = SmallThinkerConfig(**SIZES)
    params = glue.init_for(cfg, seed)["params"]
    sizes = ref.sizes_of(cfg)
    ids = np.random.default_rng(seed).integers(0, 2048, N).tolist()
    r = ref.served_token_gaps(params, ids[:N_PROMPT], ids[N_PROMPT:], sizes,
                              N, controls=ref.CONTROLS)
    assert r["gaps"].min() >= 0.0 and r["fed"] == N - 1
    tail, ringed = ref.probe_positions(r["fed"], 512)
    assert tail[0] == N - 1 - 256 and ringed[256] == N - 1 - 512
    base = {"index": 0, "n": N - N_PROMPT, "n_prompt": N_PROMPT,
            "argmax_equal": 0, "logit_std": r["logit_std"],
            "cache_tokens_ok": True, "wrapped": True,
            "ring_pages_held": 33, "ring_pages": 33}
    short = dict(base, index=1, wrapped=False, ring_pages_held=5,
                 max_gap=0.0, full_err=0.0, ring_err=0.0)
    rows = {"float32": dict(base, max_gap=0.0, argmax_equal=N - N_PROMPT,
                            full_err=0.0, ring_err=0.0)}
    for name in ref.CONTROLS:
        rows[name] = dict(
            base, max_gap=float(r[f"control_{name}_gaps"].max()),
            full_err=runner._rel_err(r[f"control_{name}_full"], r["full"]),
            ring_err=runner._rel_err(r[f"control_{name}_window"],
                                     r["window"]))
    said, verdict = [], {}
    for name, row in rows.items():
        # beside a request that did not wrap and is exact
        verdict[name], nums = runner.compare([row, short], said.append)
        with capsys.disabled():
            print(f"\n[control] seed {seed}, {name} in the program's "
                  f"place: correct={verdict[name]} " + ", ".join(
                      f"{k} {v:.5f}" for k, v in nums.items()))
    assert all("limit" in line or "verdict" in line for line in said)
    assert verdict == {"float32": True, "fp8": False, "whole_context": False}
    # (by the window layers' rows: five of the six probed layers lie
    # behind another layer's fp8 products; a full layer 0's rows are one
    # product deep and read under the limit)
    assert rows["fp8"]["ring_err"] > runner.KV_ERR_LIMIT
    assert rows["whole_context"]["ring_err"] > runner.KV_ERR_LIMIT
    # a sample without a request that wrapped (or without one that did
    # not) is not a check of both kinds of ring: not correct
    assert not runner.compare([rows["float32"]], said.append)[0]
    assert not runner.compare([short], said.append)[0]
    # rows kept in 8 bits: their rounding alone
    rounding = runner._rel_err(ref.fp8(r["full"]), r["full"])
    with capsys.disabled():
        print(f"[control] rows rounded to 8 bits read {rounding:.5f}")
    assert rounding > 0.02
