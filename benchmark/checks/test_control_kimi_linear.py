"""The controls of the Kimi-Linear cell's correctness check, kept as a
test: the plain reference computed in a precision below the one the
configuration states, put in the program's place, has to come out as NOT
correct through the runner's ``compare``, by one of its limits:

* fp8 matrix products (the nearest precision below the bfloat16 that the
  configuration states for weights and activations): by the state error,
  and on most seeds by the gap under the row maximum too;
* a bfloat16 KDA state between tokens (the configuration states float32):
  by the float32 share of the state it leaves. Its logits and its state
  lie closer to the float32 reference than the program's own do (PERF.md
  section 2), so no distance tells it; the bit patterns of the state do.

The float32 reference's own greedy tokens and state have to pass, so
that the limits are not merely tight.

Sizes: the published widths (hidden 2304, 32 KDA heads of 128, latent
512 + 64, expert width 1024, router over 256, 8 a token), one period K K
K M twice (8 layers), 8 experts held, the 40,960 rows of the vocabulary,
two requests of 64 + 960 tokens: what a CPU holds. The seeds are not
picked: the second is the one that read 0.646 on the gap alone, under
that limit (PR 28's first hand-in left it out)."""

import numpy as np
import pytest

from benchmark.reference import kimi_linear_glue as glue
from benchmark.reference import kimi_linear_ref as ref
from benchmark.runners import serve_llm_kimi_linear as runner

SIZES = dict(vocab_size=40960, num_hidden_layers=8, experts_held=(0, 8),
             max_seq_len=1024)


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_lower_precision_in_the_programs_place_is_not_correct(seed, capsys):
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    cfg = KimiLinearConfig(**SIZES)
    params = glue.init_for(cfg, seed)["params"]
    sizes = ref.sizes_of(cfg)
    rng = np.random.default_rng(seed)
    rows = {name: [] for name in ("float32", "fp8", "bf16_state")}
    for index in range(2):          # the cell compares the worst of four
        ids = rng.integers(0, 40960, 1024).tolist()
        r = ref.served_token_gaps(params, ids[:64], ids[64:], sizes, 1024,
                                  controls=ref.CONTROLS)
        assert r["gaps"].min() >= 0.0
        want, want_share = runner._probe(r["state"])
        base = {"index": index, "n": 960, "argmax_equal": 0,
                "logit_std": r["logit_std"], "state_tokens_ok": True}
        # the float32 reference's own greedy tokens and state
        rows["float32"].append(dict(
            base, max_gap=0.0, argmax_equal=960, state_err=0.0,
            state_f32_share=float(want_share)))
        # what each control would have served and left in the slot
        for name, *_ in ref.CONTROLS:
            low, share = runner._probe(r[f"control_{name}_state"])
            rows[name].append(dict(
                base, max_gap=float(r[f"control_{name}_gaps"].max()),
                state_err=runner._state_err(low, want),
                state_f32_share=float(share)))
    said, verdict = [], {}
    for name, its in rows.items():
        verdict[name], nums = runner.compare(its, said.append)
        with capsys.disabled():
            print(f"\n[control] seed {seed}, {name} in the program's "
                  f"place: correct={verdict[name]} " + ", ".join(
                      f"{k} {v:.5f}" for k, v in nums.items()))
    assert all("limit" in line for line in said)
    assert verdict == {"float32": True, "fp8": False, "bf16_state": False}
    # each by the limit that is there for it
    assert all(r["state_err"] > runner.STATE_ERR_LIMIT for r in rows["fp8"])
    assert all(r["state_f32_share"] < runner.STATE_F32_SHARE_LEAST
               for r in rows["bf16_state"])
