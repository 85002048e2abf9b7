"""LongCat-Flash at a tiny size on the CPU, against the plain reference
(benchmark/reference/longcat_flash_ref.py: float32 at 'highest', attention
a head at a time with every head's keys and values built, a loop over the
experts held, the identity experts as one weighted copy of the token), and
the reference against the source's own module (``transformers``'
``LongcatFlashForCausalLM``, the same weights copied in). Logits, layer
outputs and cached rows are compared, never sampled tokens.

Tolerances: everything here runs in float32 with 'highest' products
(tests/conftest.py), so the two sides differ by the order of their sums
only: 2e-5 absolute on logits of spread ~0.2 and on cached rows of size
~1 (~3.5 with the latent's scale)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import longcat_flash_glue as glue
from benchmark.reference import longcat_flash_ref as ref
from ray_tpu.models.longcat_flash import (LongcatFlashConfig,
                                          LongcatFlashModel, cache_spec)
from ray_tpu.models.mla import MLAMixer
from ray_tpu.ops import attention as A
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import RoutedExperts

TOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = LongcatFlashConfig.tiny()
    return cfg, glue.init_for(cfg, 11)


def _rows(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


@pytest.mark.parametrize("S", [7, 100, 300])
def test_full_forward_equals_the_reference(tiny, S):
    """2 x 100 tokens take the routed layer's whole-row product, 2 x 300
    its sorted one (more than moe.WHOLE_ROWS_BELOW)."""
    cfg, params = tiny
    ids = np.random.default_rng(1).integers(0, 512, (2, S))
    out = LongcatFlashModel(cfg).apply(params, jnp.asarray(ids, jnp.int32))
    sizes = ref.sizes_of(cfg)
    for b in range(2):
        want = ref.forward(params["params"], ids[b], sizes)
        assert float(jnp.std(want)) > 0.05
        np.testing.assert_allclose(out[b], want, atol=TOL)


def test_the_published_configuration_and_what_it_caches():
    """The defaults are the source's numbers: 56 cached sublayers for 28
    layers, q times 2 and the latent times 12^1/2, plain rotary."""
    cfg = LongcatFlashConfig()
    assert (cfg.q_scale, round(cfg.latent_scale, 4)) == (2.0, 3.4641)
    assert cfg.rope.factor == 1.0 and cfg.rope.softmax_mscale == 1.0
    np.testing.assert_allclose(
        cfg.rope.inv_freq(), 1e7 ** (-np.arange(32) / 32.0), rtol=1e-6)
    assert cache_spec(cfg)["pages"]["kv_pages"]["layers"] == 56
    cut = LongcatFlashConfig(num_layers=4, vocab_size=16384,
                             experts_held=(0, 16))
    spec = cache_spec(cut)
    assert spec["pages"]["kv_pages"] == {"layers": 8, "row": 640,
                                         "latent_rank": 512,
                                         "dtype": jnp.bfloat16}
    assert spec["state"] == {} and spec["expert_counts"] == (4, 16)
    # the real experts and the zero-compute outputs told apart
    assert spec["routed_experts"] == (12, 512, 16, 6144, 2, 256)
    hash(cut)       # flax wants a module's attributes hashable


# ------------------------------------------------- the two LoRA scales

def _ref_mla(cfg, p, x):
    with jax.default_matmul_precision("highest"):
        return ref.mla(p, x, dict(ref.sizes_of(cfg)), ref._mm(None))


def test_the_two_scales_agree_through_all_three_attention_paths():
    """One sequence of 26 tokens through the mixer four ways: cache-free
    (materialised keys and values), a prefill of 21 into pages
    (materialised, rows written), 3 more as ONE window over the pool
    (gathered rows, every head's keys built from them) and 2 more one at
    a time (absorbed: the query through W_uk against the row as the pool
    holds it). All equal the reference's rows, whose q is scaled after
    W_qb and whose latent before W_kvb; the pool holds the SCALED latent;
    and without the scales the rows are others."""
    cfg = LongcatFlashConfig.tiny()
    assert cfg.q_scale != 1.0 and cfg.latent_scale != 1.0
    mixer = MLAMixer(cfg)
    x = _rows(np.random.default_rng(5), 1, 26, 64)
    params = mixer.init(jax.random.PRNGKey(2), x)
    want, cached = _ref_mla(cfg, params["params"], x[0])
    full, _ = mixer.apply(params, x)
    np.testing.assert_allclose(full[0], want, atol=TOL)
    pages = jnp.zeros((2, 5, 8, 128))
    tables = jnp.array([[3, 1, 4, 2]])
    pre = jnp.pad(x[:, :21], ((0, 0), (0, 11), (0, 0)))
    y, pages = mixer.apply(params, pre, pages, tables, jnp.array([0]),
                           (jnp.arange(32) < 21)[None], 1)
    np.testing.assert_allclose(y[0, :21], want[:21], atol=TOL)
    win = jnp.pad(x[:, 21:24], ((0, 0), (0, 5), (0, 0)))
    y, pages = mixer.apply(params, win, pages, tables, jnp.array([21]),
                           (jnp.arange(8) < 3)[None], 1)
    np.testing.assert_allclose(y[0, :3], want[21:24], atol=TOL)
    for t in (24, 25):
        y, pages = mixer.apply(params, x[:, t:t + 1], pages, tables,
                               jnp.array([t]), None, 1)
        np.testing.assert_allclose(y[0, 0], want[t], atol=TOL)
    got = A.paged_gather(pages, tables, 1)[0, :26, :40]
    np.testing.assert_allclose(got, cached, atol=TOL)
    assert float(jnp.abs(pages[0]).max()) == 0.0   # the other sublayer's
    # the latent part is RMS ~ latent_scale, not ~1
    assert abs(float(jnp.sqrt(jnp.mean(got[:, :32] ** 2)))
               - cfg.latent_scale) < 0.2
    plain = LongcatFlashConfig.tiny(mla_scale_q_lora=False,
                                    mla_scale_kv_lora=False)
    other, _ = MLAMixer(plain).apply(params, x)
    assert float(jnp.abs(other - full).max()) > 100 * TOL


def test_a_prompt_over_its_logits_budget_walks_the_keys_in_one_kernel(
        monkeypatch):
    """A prompt of 200 tokens (padded to 256) into a context of 384
    positions: with the mixer's own ``prompt_logits_bytes`` under a query
    block's float32 logits the keys are walked in blocks inside
    ``latent_prefill_attention`` (here interpreted), a call a sublayer,
    scales and rotation as on the plain path: the reference's rows
    either way; at the published budget (256 MiB) a tiny prompt stays
    plain, and the cell's (1, 2048) over 3,072 positions does not."""
    assert LongcatFlashConfig().prompt_logits_bytes \
        < 64 * 512 * 3072 * 4 < A.LATENT_LOGITS_BYTES
    calls, kernel = [], A.latent_prefill_attention
    monkeypatch.setattr(A, "latent_prefill_attention",
                        lambda *a: calls.append(a[0].shape) or kernel(*a))
    x = _rows(np.random.default_rng(8), 1, 200, 64)
    pre = jnp.pad(x, ((0, 0), (0, 56), (0, 0)))
    tables = jnp.arange(1, 49)[None]
    valid = (jnp.arange(256) < 200)[None]
    rows = {}
    for budget in (1 << 28, 1 << 18):
        cfg = LongcatFlashConfig.tiny(prompt_logits_bytes=budget)
        mixer = MLAMixer(cfg)
        params = mixer.init(jax.random.PRNGKey(2), x[:, :8])
        want, cached = _ref_mla(cfg, params["params"], x[0])
        y, pages = mixer.apply(params, pre, jnp.zeros((2, 49, 8, 128)),
                               tables, jnp.array([0]), valid, 1)
        np.testing.assert_allclose(y[0, :200], want, atol=TOL)
        np.testing.assert_allclose(
            A.paged_gather(pages, tables, 1)[0, :200, :40], cached, atol=TOL)
        rows[budget] = y[0, :200]
    assert calls == [(1, 256, 2, 24)]       # the small budget's pass alone
    np.testing.assert_allclose(rows[1 << 18], rows[1 << 28], atol=TOL)


def test_rows_written_after_a_nonzero_start_are_the_references(tiny):
    """A prompt of 37 tokens goes into the pool as 13 and then 24: the
    pool's layer 2 i + j holds layer i's attention j's rows (the source's
    cache order), each the reference's (scaled c, RoPE(k_r)) at its
    absolute position."""
    cfg, params = tiny
    ids = np.random.default_rng(7).integers(0, 512, 37)
    model = LongcatFlashModel(cfg)
    pages = jnp.zeros((2 * cfg.num_layers, 9, 8, 128), jnp.float32)
    table = jnp.array([[5, 2, 7, 1, 3]])
    cache = {"kv_pages": pages, "block_tables": table}
    for start, stop, bucket in ((0, 13, 16), (13, 37, 32)):
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :stop - start] = ids[start:stop]
        valid = (jnp.arange(bucket) < stop - start)[None]
        _, cache, counts, zeros = model.apply(
            params, jnp.asarray(tokens), cache=cache,
            seq_lengths=jnp.array([start]), valid=valid)
    assert counts.shape == (2, 4) and zeros.shape == (2,)
    got = jnp.stack([A.paged_gather(cache["kv_pages"], table, layer)[0]
                     for layer in range(2 * cfg.num_layers)])
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    _, want = ref.forward(params["params"], ids, ref.sizes_of(cfg),
                          latents_at=np.arange(37))
    assert want.shape == (4, 37, width)
    np.testing.assert_allclose(got[:, :37, :width], want, atol=TOL)
    # the four sublayers' rows all differ (none written twice)
    assert min(float(jnp.abs(want[a] - want[b]).max())
               for a in range(4) for b in range(a)) > 0.1
    # the second call's counts are its 24 real tokens' and no padding's
    chosen = np.asarray(ref.routing(params["params"], ids,
                                    ref.sizes_of(cfg)))[:, 13:]
    np.testing.assert_array_equal(
        zeros, (chosen >= cfg.n_routed_experts).sum(axis=(1, 2)))
    np.testing.assert_array_equal(
        counts, [[(c == e).sum() for e in range(4)] for c in chosen])


# ------------------------------------------------------------ the router

def _layer(held, experts=512, zero=256, top_k=12, d_ff=8):
    return RoutedExperts(experts, d_ff, top_k, held=held, scaling=6.0,
                         renormalize=False, dtype=jnp.float32,
                         score="softmax", zero_experts=zero)


def _ref_layer(p, x, held, experts=512, top_k=12):
    z = {"held": held, "top_k": top_k, "scaling": 6.0, "real": experts}
    with jax.default_matmul_precision("highest"):
        return ref.moe(p, x, z, ref._mm(None)), \
            np.asarray(ref.route(p, x, z)[0])


def _spread(params, key, std=0.3):
    """A router whose softmax is far from flat (N(0, 0.02) over 24 inputs
    would give every output ~1/768)."""
    return dict(params, router=std * jax.random.normal(
        key, params["router"].shape))


def test_the_32_shares_add_up_to_the_uncut_layer():
    """512 real experts 32 ways, 16 a share, and 256 zero-compute outputs,
    12 a token: what each share's own experts give, with the identity
    experts' part (which the token's own chip computes, once) counted
    once, is the whole layer, as the reference computes it uncut. The
    shares' counts are the uncut layer's, the zero-compute assignments
    are the same on every share, and every assignment is counted once."""
    x = _rows(np.random.default_rng(4), 40, 24)
    whole = _layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    assert params["router"].shape == (24, 768) \
        and params["router_bias"].shape == (768,) \
        and params["w_gate"].shape == (512, 24, 8)
    params = _spread(params, jax.random.PRNGKey(3))
    y_whole, counts_whole, zero_whole = whole.apply({"params": params}, x)
    want, chosen = _ref_layer(params, x, (0, 512))
    # the identity part alone, by hand: x times its chosen zero-compute
    # outputs' weights
    w = ref.route(params, x, {"top_k": 12, "scaling": 6.0})[1]
    zero_part = x * jnp.sum(jnp.where(chosen >= 512, w, 0.0), axis=1,
                            keepdims=True)
    total, touched = zero_part, 0
    for first in range(0, 512, 16):
        p = dict(params, **{k: params[k][first:first + 16]
                            for k in ("w_gate", "w_up", "w_down")})
        y, counts, zero = _layer((first, 16)).apply({"params": p}, x)
        np.testing.assert_array_equal(counts,
                                      counts_whole[first:first + 16])
        assert int(zero) == int(zero_whole)
        total = total + (y - zero_part)
        touched += int(counts.sum())
    assert int(zero_whole) == int((chosen >= 512).sum()) > 40
    assert touched + int(zero_whole) == 40 * 12
    np.testing.assert_allclose(total, y_whole, atol=TOL)
    np.testing.assert_allclose(y_whole, want, atol=TOL)
    assert float(jnp.abs(zero_part).max()) > 0.01


def test_a_token_of_only_zero_compute_experts_and_one_with_none():
    """Token 0 is steered to 12 zero-compute outputs, token 1 to 12 real
    experts, token 2 is left to chance: token 0's row is 6 sum(p) times
    itself and reads no expert, token 1's has no identity part, and all
    three are the reference's."""
    rng = np.random.default_rng(8)
    x = np.array(_rows(rng, 3, 24)) * 0.1
    x[0, 0], x[1, 1] = 4.0, 4.0
    layer = _layer((0, 16), experts=32, zero=16, top_k=12)
    params = layer.init(jax.random.PRNGKey(0), jnp.asarray(x))["params"]
    router = np.array(_spread(params, jax.random.PRNGKey(5))["router"])
    router[0, 32:44] += 3.0         # input 0 lights outputs 32..43 (zero)
    router[1, 2:14] += 3.0          # input 1 lights experts 2..13 (held)
    params = dict(params, router=jnp.asarray(router))
    y, counts, zero = layer.apply({"params": params}, jnp.asarray(x))
    want, chosen = _ref_layer(params, jnp.asarray(x), (0, 16), experts=32)
    assert sorted(chosen[0]) == list(range(32, 44))
    assert sorted(chosen[1]) == list(range(2, 14))
    np.testing.assert_allclose(y, want, atol=TOL)
    z = {"top_k": 12, "scaling": 6.0}
    w0 = np.asarray(ref.route(params, jnp.asarray(x), z)[1])[0]
    np.testing.assert_allclose(y[0], x[0] * w0.sum(), atol=TOL)
    assert int(zero) == 12 + int((chosen[2] >= 32).sum())
    np.testing.assert_array_equal(
        counts, [(chosen[1:] == e).sum() for e in range(16)])
    # without token 2, nothing but token 1's 12 experts is touched
    _, only, zero = layer.apply({"params": params}, jnp.asarray(x),
                                valid=jnp.array([True, True, False]))
    assert int(zero) == 12 and int(only.sum()) == 12 \
        and int((only > 0).sum()) == 12


@pytest.mark.parametrize("T,held,product,chunk_rows", [
    (64, 4, "touched_kernel", 64), (2048, 4, "grouped_kernel", 9216),
    (2048, 1, "grouped_kernel", 256)], ids=["touched", "whole", "chunks"])
def test_both_products_leave_the_zero_compute_assignments_out(
        monkeypatch, T, held, product, chunk_rows):
    """4 (or 1) of 16 real experts held beside 8 zero-compute outputs, 4
    a token: the layer gives what the reference's dense loop gives,
    padding tokens left out; ``counts`` holds the real assignments that
    landed here and nothing of the zero-compute ones, so the rows
    multiplied (``rows_multiplied``) are those of the real assignments'
    blocks. With 1 of the router's 24 outputs held the worst case (8,448
    rows) is 11 times what even routing sends here (768), and the sorted
    rows go through the loop over live chunks (here a block a chunk)."""
    monkeypatch.setattr(moe, "CHUNK_BYTES", 50_000)
    x = _rows(np.random.default_rng(6), T, 24)
    layer = _layer((0, held), experts=16, zero=8, top_k=4, d_ff=32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params = _spread(params, jax.random.PRNGKey(9), 0.2)
    real = T - 5
    plan = moe.expert_product(T, 4, 16, held, 24, 4, 8)
    assert plan[::2] == (product, chunk_rows)
    y, counts, zero = layer.apply({"params": params}, x,
                                  valid=jnp.arange(T) < real)
    want, chosen = _ref_layer(params, x, (0, held), experts=16, top_k=4)
    np.testing.assert_allclose(y[:real], want[:real], atol=TOL)
    chosen = chosen[:real]
    np.testing.assert_array_equal(
        counts, [(chosen == e).sum() for e in range(held)])
    assert int(zero) == int((chosen >= 16).sum()) > T // 2
    by_hand = int((np.asarray(counts) > 0).sum()) * plan.block_rows \
        if product == "touched_kernel" else int(sum(
            -(-int(c) // plan.block_rows) for c in counts)) * plan.block_rows
    assert plan.rows_multiplied(counts) == by_hand
    if held == 1:       # more live rows than a chunk holds
        assert by_hand > plan.chunk_rows
    # a router of the same width whose outputs are all real experts would
    # have sent those assignments to rows: here they take none
    assert int(counts.sum()) + int(zero) < real * 4


def test_the_block_rule_reckons_with_the_routers_whole_width():
    """2,048 tokens, 12 of 768 outputs each: an expert expects 32 rows, a
    block of 128; reckoned over the 512 real ones alone (48 a block of
    128 still) the rule would be the same here, but at 8,192 tokens 128
    against 192: the zero-compute outputs take their share."""
    assert moe.expert_product(2048, 12, 512, 16, 6144, 2, 256).block_rows \
        == 128
    assert moe.expert_product(8192, 12, 512, 16, 6144, 2, 256).block_rows \
        == 128
    assert moe.expert_product(8192, 12, 512, 16, 6144, 2).block_rows == 256


# --------------------- the models that do not change keep their trees

def _tree_digest(params):
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    text = "\n".join(f"{jax.tree_util.keystr(path)} {leaf.shape} "
                     f"{leaf.dtype}" for path, leaf in leaves)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("module,model,config,digest", [
    ("kimi_k2", "KimiK2Model", "KimiK2Config", "4be48c2b31be9553"),
    ("kimi_linear", "KimiLinearModel", "KimiLinearConfig", "afb5ae96b3745838"),
    ("laguna", "LagunaModel", "LagunaConfig", "155f72cd4000c471"),
])
def test_the_new_fields_at_their_defaults_change_no_parameter(
        module, model, config, digest):
    """``RoutedExperts.score`` / ``zero_experts`` and ``MLAMixer``'s
    scales at their defaults: the three measured models' parameter trees
    (every name, shape and dtype, tiny presets) are what they were at
    the parent commit (digests taken there), so their checkpoints and
    the benchmark's glue files load as before."""
    import importlib
    mod = importlib.import_module("ray_tpu.models." + module)
    cfg = getattr(mod, config).tiny()
    shapes = jax.eval_shape(getattr(mod, model)(cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    assert _tree_digest(shapes) == digest


# ------------------------------------------------ the source's own module

def test_the_reference_equals_transformers_own_module():
    """``transformers.LongcatFlashForCausalLM`` (float32, eager attention)
    at hidden 32, 2 layers, 6 real + 3 zero-compute experts, 3 a token,
    its weights copied into the program's tree: the reference, given the
    uncut share, gives the module's logits. Norm gains and the selection
    bias are set off their initial 1 and 0 so that they count."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "LongcatFlashForCausalLM"):
        pytest.skip("this transformers has no longcat_flash")
    sizes = dict(
        vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=2,
        q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, ffn_hidden_size=48,
        expert_ffn_hidden_size=16, n_routed_experts=6, zero_expert_num=3,
        moe_topk=3, routed_scaling_factor=6.0, rope_theta=1e4,
        rms_norm_eps=1e-5)
    hf_cfg = transformers.LongcatFlashConfig(
        **sizes, num_hidden_layers=4, head_dim=4,
        max_position_embeddings=128, attn_implementation="eager")
    torch.manual_seed(0)
    hf = transformers.LongcatFlashForCausalLM(hf_cfg).float().eval()
    with torch.no_grad():
        for name, p in hf.named_parameters():
            if "norm" in name:
                p.add_(0.1 * torch.randn_like(p))
            elif "classifier" in name:
                p.mul_(15.0)        # a softmax far from flat
        for layer in hf.model.layers:
            layer.mlp.router.e_score_correction_bias.copy_(
                0.05 * torch.randn(9))

    def t(w):       # a torch Linear's [out, in] -> the program's [in, out]
        return jnp.asarray(w.detach().numpy().T)

    def gain(m):
        return {"scale": jnp.asarray(m.weight.detach().numpy())}

    def mlp(m):
        return {"gate": t(m.gate_proj.weight), "up": t(m.up_proj.weight),
                "down": t(m.down_proj.weight)}
    params = {"embed": jnp.asarray(
        hf.model.embed_tokens.weight.detach().numpy()),
        "final_norm": gain(hf.model.norm), "lm_head": t(hf.lm_head.weight)}
    for i, layer in enumerate(hf.model.layers):
        p = {}
        for j in (0, 1):
            a = layer.self_attn[j]
            p[f"attn_norm_{j}"] = gain(layer.input_layernorm[j])
            p[f"ffn_norm_{j}"] = gain(layer.post_attention_layernorm[j])
            p[f"mla_{j}"] = {
                "q_a": t(a.q_a_proj.weight), "q_norm": gain(a.q_a_layernorm),
                "q_b": t(a.q_b_proj.weight),
                "kv_a": t(a.kv_a_proj_with_mqa.weight),
                "kv_norm": gain(a.kv_a_layernorm),
                "kv_b": t(a.kv_b_proj.weight), "o_proj": t(a.o_proj.weight)}
            p[f"mlp_{j}"] = mlp(layer.mlps[j])
        experts = [mlp(layer.mlp.experts[e]) for e in range(6)]
        p["moe"] = {
            "router": t(layer.mlp.router.classifier.weight),
            "router_bias": jnp.asarray(
                layer.mlp.router.e_score_correction_bias.numpy()),
            **{"w_" + k: jnp.stack([e[k] for e in experts])
               for k in ("gate", "up", "down")}}
        params[f"layers_{i}"] = p
    ids = np.random.default_rng(3).integers(0, 64, (2, 40))
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    z = ref.sizes_of(dict(sizes, mla_scale_q_lora=True,
                          mla_scale_kv_lora=True, experts_held=None))
    chosen = np.asarray(ref.routing(params, ids[0], z))
    assert (chosen >= 6).any() and (chosen < 6).any()
    for b in range(2):
        ours = ref.forward(params, ids[b], z)
        assert float(np.std(theirs[b])) > 0.05
        np.testing.assert_allclose(ours, theirs[b], atol=TOL)
    # and the program's own full forward, on the same weights
    cfg = LongcatFlashConfig(**sizes, max_seq_len=128, dtype=jnp.float32)
    out = LongcatFlashModel(cfg).apply({"params": params},
                                       jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(out, theirs, atol=TOL)
