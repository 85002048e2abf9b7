"""The control of the LongCat-Flash cell's correctness check, kept as a
test: the plain reference computed in fp8 matrix products (the nearest
precision below the bfloat16 that the configuration states for weights,
activations and cached rows), put in the program's place, has to come
out as NOT correct through the runner's ``compare``, by one of its
limits: by the cached latent rows' error on every seed. The float32
reference's own greedy tokens and rows have to pass, so that the limits
are not merely tight.

Sizes: the published widths (hidden 6144, 64 heads of 128 + 64 and 128,
query rank 1536, latent 512 + 64, both LoRA scales, dense width 12288,
expert width 2048, a softmax router over 512 + 256, 12 a token), ONE
double layer, 16 experts held, 2,048 rows of the vocabulary, two requests
of 64 + 192 tokens: what a CPU holds. The seeds are not picked."""

import numpy as np
import pytest

from benchmark.reference import longcat_flash_glue as glue
from benchmark.reference import longcat_flash_ref as ref
from benchmark.runners import serve_llm_longcat_flash as runner

SIZES = dict(vocab_size=2048, num_layers=1, experts_held=(0, 16),
             max_seq_len=256)


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_fp8_in_the_programs_place_is_not_correct(seed, capsys):
    from ray_tpu.models.longcat_flash import LongcatFlashConfig
    cfg = LongcatFlashConfig(**SIZES)
    params = glue.init_for(cfg, seed)["params"]
    sizes = ref.sizes_of(cfg)
    rng = np.random.default_rng(seed)
    rows = {"float32": [], "fp8": []}
    for index in range(2):          # the cell compares the worst of four
        ids = rng.integers(0, 2048, 256).tolist()
        r = ref.served_token_gaps(params, ids[:64], ids[64:], sizes, 256,
                                  control=ref.fp8)
        assert r["gaps"].min() >= 0.0 and r["latents"].shape[0] == 2
        base = {"index": index, "n": 192, "argmax_equal": 0,
                "logit_std": r["logit_std"], "cache_tokens_ok": True}
        rows["float32"].append(dict(base, max_gap=0.0, argmax_equal=192,
                                    latent_err=0.0))
        rows["fp8"].append(dict(
            base, max_gap=float(r["control_gaps"].max()),
            latent_err=runner._rel_err(r["control_latents"],
                                       r["latents"])))
    said, verdict = [], {}
    for name, its in rows.items():
        verdict[name], nums = runner.compare(its, said.append)
        with capsys.disabled():
            print(f"\n[control] seed {seed}, {name} in the program's "
                  f"place: correct={verdict[name]} " + ", ".join(
                      f"{k} {v:.5f}" for k, v in nums.items()))
    assert all("limit" in line for line in said)
    assert verdict == {"float32": True, "fp8": False}
    assert all(r["latent_err"] > runner.LATENT_ERR_LIMIT
               for r in rows["fp8"])
