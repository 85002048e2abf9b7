"""The controls of the Jamba cell's correctness check, kept as a test:
the plain reference computed in a precision below the one the
configuration states, put in the program's place, has to come out as NOT
correct through the runner's ``compare``, by one of its limits:

* fp8 matrix products (the nearest precision below the bfloat16 that the
  configuration states for weights and activations): by the state error;
* a bfloat16 Mamba state between tokens (the configuration states
  float32): by the float32 share of the state it leaves (the bit patterns
  of the state tell it in every case; its distance lies near the
  program's own).

The float32 reference's own greedy tokens and state have to pass, so
that the limits are not merely tight.

Sizes: the published widths (hidden 2,560, d_inner 5,120, 16 states,
dt rank 160, 20 heads of 128 over one, feed-forward 8,192), layers 0-7
(seven Mamba layers and the attention layer at 7), 8,192 rows of the
vocabulary, two requests of 64 + 448 tokens: what a CPU holds. The seeds
are not picked."""

import numpy as np
import pytest

from benchmark.reference import jamba_glue as glue
from benchmark.reference import jamba_ref as ref
from benchmark.runners import serve_llm_jamba as runner

SIZES = dict(vocab_size=8192, num_hidden_layers=8, max_seq_len=512)


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_lower_precision_in_the_programs_place_is_not_correct(seed, capsys):
    import jax.numpy as jnp

    from ray_tpu.models.jamba import JambaConfig
    cfg = JambaConfig(**SIZES)
    assert cfg.layer_kinds().count("attention") == 1
    params = glue.init_for(cfg, seed)["params"]
    sizes = ref.sizes_of(cfg)
    rng = np.random.default_rng(seed)
    rows = {name: [] for name in ("float32", "fp8", "bf16_state")}

    def probed(state):          # the reference's state is [.., d_in, N]
        got, share = runner._probe(jnp.swapaxes(state, 1, 2))
        return np.asarray(got), float(share)
    for index in range(2):          # the cell compares the worst of four
        ids = rng.integers(0, 8192, 512).tolist()
        r = ref.served_token_gaps(params, ids[:64], ids[64:], sizes, 512,
                                  controls=ref.CONTROLS)
        assert r["gaps"].min() >= 0.0
        want, want_share = probed(r["state"])
        base = {"index": index, "n": 448, "n_prompt": 64, "argmax_equal": 0,
                "logit_std": r["logit_std"], "state_tokens_ok": True}
        # the float32 reference's own greedy tokens and state
        rows["float32"].append(dict(
            base, max_gap=0.0, argmax_equal=448, state_err=0.0,
            state_f32_share=want_share))
        # what each control would have served and left in the slot
        for name, *_ in ref.CONTROLS:
            low, share = probed(r[f"control_{name}_state"])
            rows[name].append(dict(
                base, max_gap=float(r[f"control_{name}_gaps"].max()),
                state_err=runner._state_err(low, want),
                state_f32_share=share))
    said, verdict = [], {}
    for name, its in rows.items():
        verdict[name], nums = runner.compare(its, said.append)
        with capsys.disabled():
            print(f"\n[control] seed {seed}, {name} in the program's "
                  f"place: correct={verdict[name]} " + ", ".join(
                      f"{k} {v:.5f}" for k, v in nums.items()))
    assert all("limit" in line or "verdict" in line for line in said)
    assert verdict == {"float32": True, "fp8": False, "bf16_state": False}
    # each by the limit that is there for it
    assert all(r["state_err"] > runner.STATE_ERR_LIMIT for r in rows["fp8"])
    assert all(r["state_f32_share"] < runner.STATE_F32_SHARE_LEAST
               for r in rows["bf16_state"])
