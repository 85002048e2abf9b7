"""LFM2-MoE at a tiny size on the CPU, against the plain reference
(benchmark/reference/lfm2_ref.py: float32 at 'highest', the convolution
by its three taps over the whole sequence, whole-sequence attention with
the query/key norms before the rotary, the router in the published
order). Logits and tails are compared, never sampled tokens.

Tolerances: everything here runs in float32 with 'highest' products
(tests/conftest.py), so the two sides differ by the order of their sums
only. 5e-5 absolute on logits of spread ~0.16. A bias that weighs, a norm
after the rotary or a bfloat16 product moves them by 1e-3 and more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_glue as glue
from benchmark.reference import lfm2_ref as ref
from ray_tpu.models.lfm2 import Lfm2Config, Lfm2Model, cache_spec
from ray_tpu.parallel import moe

TOL = 5e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = Lfm2Config.tiny()
    return cfg, glue.init_for(cfg, 3000000019), ref.sizes_of(cfg)


@pytest.mark.parametrize("S", [40, 100])
def test_full_forward_equals_the_reference(tiny, S):
    cfg, params, sizes = tiny
    ids = np.random.default_rng(S).integers(0, cfg.vocab_size, (2, S))
    got = Lfm2Model(cfg).apply(params, jnp.asarray(ids, jnp.int32))
    for b in range(2):
        want = ref.forward(params["params"], ids[b], sizes)
        np.testing.assert_allclose(got[b], want, atol=TOL)
    assert float(jnp.std(got)) > 0.05


def test_the_weights_come_from_the_seed(tiny):
    cfg, params, _ = tiny
    again = glue.init_for(cfg, 3000000019)
    other = glue.init_for(cfg, 3000000020)
    same = jax.tree_util.tree_map(lambda a, b: bool(jnp.all(a == b)),
                                  params, again)
    assert all(jax.tree_util.tree_leaves(same))
    layer = params["params"]["layers_3"]
    assert float(jnp.abs(layer["conv"]["in_proj"] - other["params"][
        "layers_3"]["conv"]["in_proj"]).max()) > 0
    # the expert bias is DRAWN (a zero bias tells no weighing bias from
    # a choosing one), the taps have a convolution's size, gains lie
    # round one
    bias = np.asarray(layer["moe"]["router_bias"])
    assert bias.dtype == np.float32 and 0.02 < np.abs(bias).max() < 0.5
    assert 0.2 < float(jnp.std(layer["conv"]["conv"])) < 0.8
    gain = np.asarray(params["params"]["layers_2"]["attn"]["q_norm"]["scale"])
    assert gain.shape == (16,) and 0 < np.abs(gain - 1).max() < 0.1


def test_the_layer_kinds_of_the_published_24_layers(tiny):
    cfg, params, _ = tiny
    full = Lfm2Config()
    assert [i for i, k in enumerate(full.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert full.layer_types.count("conv") == 18 and full.n_layers == 24
    assert [i for i, k in enumerate(full.ffn_kinds()) if k == "dense"] \
        == [0, 1]
    assert full.head_dim == 64
    # the published lists are given whole and the first layers kept
    cut = Lfm2Config(num_hidden_layers=16)
    assert cut.layer_types == full.layer_types[:16]
    assert cut.layer_types.count("full_attention") == 4
    assert cut.ffn_kinds().count("routed") == 14
    # operator and feed-forward part vary independently: all three kinds
    # of block at tiny()
    p = params["params"]
    assert set(p) == {"embed", "embedding_norm"} | {
        f"layers_{i}" for i in range(8)}
    assert set(p["layers_0"]) == {"operator_norm", "conv", "ffn_norm", "mlp"}
    assert set(p["layers_3"]) == {"operator_norm", "conv", "ffn_norm", "moe"}
    assert set(p["layers_2"]) == {"operator_norm", "attn", "ffn_norm", "moe"}
    assert p["layers_3"]["conv"]["in_proj"].shape == (64, 192)
    assert p["layers_3"]["conv"]["conv"].shape == (3, 64)
    assert p["layers_2"]["attn"]["q_proj"].shape == (64, 128)
    assert p["layers_2"]["attn"]["k_proj"].shape == (64, 32)
    with pytest.raises(ValueError, match="as published"):
        Lfm2Config(conv_bias=True)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2Config(layer_types=("conv", "mamba"), num_hidden_layers=2)


def _count(cfg):
    shapes = jax.eval_shape(Lfm2Model(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return shapes, sum(int(np.prod(a.shape))
                       for a in jax.tree_util.tree_leaves(shapes))


def test_the_published_sizes_add_up():
    """8,339.9 M parameters with the head tied (published: 8.3 B), and
    the 16 layers the cell holds 5,399.1 M (ISSUE 53's table), counted
    from the shapes the model itself declares."""
    shapes, n = _count(Lfm2Config())
    assert n == 8_339_930_560
    per = lambda t: sum(int(np.prod(a.shape))       # noqa: E731
                        for a in jax.tree_util.tree_leaves(t))
    p = shapes["params"]
    assert per(p["layers_0"]["conv"]) == 16_783_360        # 16.78 M
    assert per(p["layers_2"]["attn"]) == 10_485_888        # 10.49 M
    assert per(p["layers_0"]["mlp"]) == 44_040_192         # 44.04 M
    assert per(p["layers_2"]["moe"]) == 352_387_104        # 352.39 M
    assert per(p["embed"]) == 134_217_728                  # once: tied
    assert _count(Lfm2Config(num_hidden_layers=16))[1] == 5_399_129_024


def test_cache_spec_states_pages_for_attention_and_a_tail_for_the_rest():
    spec = cache_spec(Lfm2Config(num_hidden_layers=16))
    assert set(spec["pages"]) == {"k_pages", "v_pages"}
    assert spec["pages"]["k_pages"]["layers"] == 4
    assert spec["pages"]["k_pages"]["row"] == 512
    assert spec["pages"]["k_pages"]["q_heads"] == 32
    assert spec["pages"]["k_pages"]["head_dim"] == 64
    # a state that is a tail alone: no array names a recurrence
    assert set(spec["state"]) == {"conv_tail"}
    assert spec["state"]["conv_tail"]["shape"] == (12, 2, 2048)
    assert "recurrence" not in spec["state"]["conv_tail"]
    assert spec["expert_counts"] == (14, 32)
    assert spec["routed_experts"] == (4, 32, 32, 2048, 2)
    # 8 KiB a cached token over the stage
    assert 4 * 2 * 512 * 2 == 8 << 10


def _cache(cfg, B, slots, bs=8, nb=16, n_slots=5):
    spec = cache_spec(cfg)
    page, tail = spec["pages"]["k_pages"], spec["state"]["conv_tail"]
    cache = {
        "k_pages": jnp.zeros((page["layers"], 1 + B * nb, bs, page["row"]),
                             cfg.dtype),
        # [layers, slots, ...]: the null slot and four more
        "conv_tail": jnp.zeros(
            (tail["shape"][0], n_slots, *tail["shape"][1:]), cfg.dtype),
        "block_tables": jnp.asarray(
            1 + np.arange(B * nb).reshape(B, nb), jnp.int32)}
    if slots is not None:
        cache["slots"] = jnp.asarray(slots[:B], jnp.int32)
    cache["v_pages"] = cache["k_pages"]
    return cache


def _served(cfg, params, prompts, n_decode, pad_to, slots=(1, 2, 3)):
    """One padded prefill step of ``prompts`` and ``n_decode`` one-token
    steps through pages and tail slots, greedy. Returns each row's logits
    rows and tokens, and the cache."""
    model = Lfm2Model(cfg)
    B = len(prompts)
    cache = _cache(cfg, B, slots)
    ids = np.zeros((B, pad_to), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for b, p in enumerate(prompts):
        ids[b, :len(p)] = p
    valid = jnp.arange(pad_to)[None, :] < lens[:, None]
    logits, cache, counts = model.apply(
        params, jnp.asarray(ids), cache=cache,
        seq_lengths=jnp.zeros((B,), jnp.int32), valid=valid,
        logits_at=jnp.asarray(lens - 1))
    # every real token through every router, top_k a token, padding none
    assert counts.shape == cache_spec(cfg)["expert_counts"]
    np.testing.assert_array_equal(
        counts.sum(axis=1), int(lens.sum()) * cfg.num_experts_per_tok)
    rows = [[np.asarray(logits[b, 0])] for b in range(B)]
    tokens = [[int(r[0].argmax())] for r in rows]
    for _ in range(n_decode):
        step = jnp.asarray([[t[-1]] for t in tokens], jnp.int32)
        logits, cache, _ = model.apply(
            params, step, cache=cache, seq_lengths=jnp.asarray(lens),
            valid=jnp.ones((B, 1), bool))
        lens = lens + 1
        for b in range(B):
            rows[b].append(np.asarray(logits[b, 0]))
            tokens[b].append(int(logits[b, 0].argmax()))
    return rows, tokens, cache


def test_prefill_then_decode_equals_the_references_full_forward(tiny):
    """Prompts of unequal length in ONE padded prefill step, then
    decoding through pages and tail slots: every logits row is the
    reference's full forward's, and each row is what it is alone."""
    cfg, params, sizes = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (37, 5, 20)]
    rows, tokens, cache = _served(cfg, params, prompts, 6, 64)
    for p, got, toks in zip(prompts, rows, tokens):
        ids = np.asarray(p + toks[:-1], np.int32)
        want = ref.forward(params["params"], ids, sizes)[len(p) - 1:]
        np.testing.assert_allclose(np.stack(got), want, atol=TOL)
    alone, _, _ = _served(cfg, params, prompts[1:2], 6, 8)
    np.testing.assert_allclose(np.stack(alone[0]), np.stack(rows[1]),
                               atol=TOL)
    # the null slot and the free slot were never written
    assert float(jnp.abs(cache["conv_tail"][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache["conv_tail"][:, 4]).max()) == 0.0
    assert float(jnp.abs(cache["conv_tail"][:, 2]).max()) > 0.0


@pytest.mark.parametrize("n_prompt,n_decode", [(1, 0), (2, 0), (1, 1),
                                               (23, 4)])
def test_the_served_tail_is_the_references(tiny, n_prompt, n_decode):
    """What a slot holds after prefill and decode is the reference's last
    two rows of ``g`` after the same tokens (what the benchmark's probe
    compares). A prompt of 1 and of 2 tokens, shorter than the kernel of
    3, leaves zeros where the sequence had not begun."""
    cfg, params, sizes = tiny
    prompt = np.random.default_rng(9).integers(0, cfg.vocab_size,
                                               n_prompt).tolist()
    _, tokens, cache = _served(cfg, params, [prompt], n_decode, 32)
    ids = np.asarray(prompt + tokens[0][:-1], np.int32)
    _, want = ref.forward(params["params"], ids, sizes,
                          state_after=len(ids) - 1)
    assert want.shape == (6, 2, 64)
    np.testing.assert_allclose(cache["conv_tail"][:, 1], want, atol=TOL)
    if len(ids) == 1:
        assert float(jnp.abs(cache["conv_tail"][:, 1, 0]).max()) == 0.0
    assert float(jnp.abs(cache["conv_tail"][:, 1, 1]).max()) > 1e-4


def test_padded_rows_and_positions_leave_tail_and_pages_bit_for_bit(tiny):
    """A padding row of a prefill bucket (nothing valid, the null slot
    and the null page) and of a decode bucket, and a by-slot decode
    step's free slot: the slots and pages they name hold afterwards what
    they held."""
    cfg, params, _ = tiny
    model = Lfm2Model(cfg)
    rng = np.random.default_rng(3)
    cache = _cache(cfg, 2, (2, 0))
    fill = lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype)  # noqa
    cache["conv_tail"] = fill(cache["conv_tail"])
    cache["k_pages"], cache["v_pages"] = (fill(cache["k_pages"]),
                                          fill(cache["v_pages"]))
    # row 1 is padding: its table names the null page alone
    cache["block_tables"] = cache["block_tables"].at[1].set(0)
    before = {k: np.asarray(cache[k])
              for k in ("conv_tail", "k_pages", "v_pages")}
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    valid = jnp.arange(16)[None, :] < jnp.asarray([9, 0])[:, None]
    _, after, _ = model.apply(params, ids, cache=cache,
                              seq_lengths=jnp.zeros((2,), jnp.int32),
                              valid=valid, logits_at=jnp.asarray([8, 0]))
    got = np.asarray(after["conv_tail"])
    np.testing.assert_array_equal(got[:, [0, 1, 3, 4]],
                                  before["conv_tail"][:, [0, 1, 3, 4]])
    assert np.abs(got[:, 2] - before["conv_tail"][:, 2]).max() > 1e-3
    for k in ("k_pages", "v_pages"):
        # row 0's 9 tokens lie in pages 1 and 2 (positions 0-8); the
        # padded positions 9-15 of page 2 and every other page are
        # untouched
        # (the null page 0 is where the padding row's rows go)
        was, now = before[k], np.asarray(after[k])
        np.testing.assert_array_equal(now[:, 3:], was[:, 3:])
        np.testing.assert_array_equal(now[:, 2, 1:], was[:, 2, 1:])
        assert np.abs(now[:, 1] - was[:, 1]).max() > 1e-3
    # a row whose 9 real positions sit in a bucket of 16 ends where the
    # same 9 end in a bucket of 9
    _, exact, _ = model.apply(
        params, ids[:1, :9],
        cache=dict(_cache(cfg, 1, (2,)),
                   conv_tail=jnp.asarray(before["conv_tail"])),
        seq_lengths=jnp.zeros((1,), jnp.int32), valid=jnp.ones((1, 9), bool))
    np.testing.assert_allclose(after["conv_tail"][:, 2],
                               exact["conv_tail"][:, 2], atol=TOL)
    # a decode step in slot order (no ``slots``): row 1 is padding
    by_slot = {k: v for k, v in after.items() if k != "slots"}
    held = {k: np.asarray(by_slot[k])
            for k in ("conv_tail", "k_pages", "v_pages")}
    _, stepped, _ = model.apply(
        params, ids[:, :1], cache=by_slot,
        seq_lengths=jnp.asarray([9, 0], jnp.int32),
        valid=jnp.asarray([[True], [False]]))
    got = np.asarray(stepped["conv_tail"])
    np.testing.assert_array_equal(got[:, [0, 2, 3, 4]],
                                  held["conv_tail"][:, [0, 2, 3, 4]])
    assert np.abs(got[:, 1] - held["conv_tail"][:, 1]).max() > 1e-4
    for k in ("k_pages", "v_pages"):
        was, now = held[k], np.asarray(stepped[k])
        # position 9 of row 0: page 2, row 1 of the page; nothing else
        # but the null page 0, where a padding row's write goes
        changed = np.argwhere(np.any(now[:, 1:] != was[:, 1:], axis=(0, 3)))
        assert changed.tolist() == [[1, 1]], changed


# the reference's own wrong programs: each must FAIL the tolerance that
# the stated program passes (test_full_forward_equals_the_reference)
WRONG = {
    "a_bias_that_weighs": dict(bias_weighs=True),
    "a_norm_after_the_rotary": dict(norm_after_rotary=True),
    "bfloat16_products": dict(quant=ref.bf16),
    "fp8_products": dict(quant=ref.fp8),
}


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_program_fails_the_tolerance_the_stated_one_passes(tiny,
                                                                   wrong):
    cfg, params, sizes = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 60)
    want = ref.forward(params["params"], ids, sizes)
    got = Lfm2Model(cfg).apply(params, jnp.asarray(ids[None], jnp.int32))[0]
    np.testing.assert_allclose(got, want, atol=TOL)
    low = ref.forward(params["params"], ids, sizes, **WRONG[wrong])
    assert float(jnp.abs(low - want).max()) > 10 * TOL


def test_a_missing_epsilon_shows_at_a_near_zero_sum():
    """With every score near zero (a router whose logits are -30) the
    chosen scores add up to ~4e-13: the stated weights are ``s / (sum +
    1e-6)`` ~ 1e-7, and without the 1e-6 they add up to one. The layer
    with the epsilon equals the reference's; the layer without it (every
    other model's default) is far off."""
    rng = np.random.default_rng(0)
    E, d, ff, k, T = 8, 64, 32, 2, 12
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    layers = {eps: moe.RoutedExperts(
        E, ff, k, renormalize=True, renormalize_eps=eps, dtype=jnp.float32)
        for eps in (1e-6, 0.0)}
    params = layers[0.0].init(jax.random.PRNGKey(0), x)
    p = dict(params["params"])
    p["router"] = jnp.zeros((d, E)).at[0].set(-30.0)
    x = x.at[:, 0].set(1.0)
    p["router_bias"] = jnp.asarray(0.1 * rng.standard_normal(E), jnp.float32)
    z = dict(top_k=k, scaling=1.0)
    mm = ref._mm(None)
    want = ref.routed_experts(p, x, z, mm)
    without = ref.routed_experts(p, x, z, mm, renorm_eps=0.0)
    got, _ = layers[1e-6].apply({"params": p}, x)
    got0, _ = layers[0.0].apply({"params": p}, x)
    scale = float(jnp.abs(without).max())
    assert scale > 1e-3
    np.testing.assert_allclose(got, want, atol=1e-6 * scale)
    np.testing.assert_allclose(got0, without, atol=1e-4 * scale)
    assert float(jnp.abs(want).max()) < 1e-4 * scale


def test_the_bias_chooses_and_never_weighs():
    """A bias large enough to change the choice changes the chosen
    experts; the weights are the chosen experts' own scores over their
    sum, whatever the bias."""
    rng = np.random.default_rng(2)
    E, d, ff, k, T = 8, 64, 32, 2, 16
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    layer = moe.RoutedExperts(E, ff, k, renormalize=True,
                              renormalize_eps=1e-6, dtype=jnp.float32)
    p = dict(layer.init(jax.random.PRNGKey(1), x)["params"])
    p["router"] = jnp.asarray(rng.standard_normal((d, E)), jnp.float32)
    p["router_bias"] = jnp.zeros((E,)).at[3].set(5.0)   # 3 is always chosen
    y, counts = layer.apply({"params": p}, x)
    assert int(counts[3]) == T
    z = dict(top_k=k, scaling=1.0)
    want = ref.routed_experts(p, x, z, ref._mm(None))
    np.testing.assert_allclose(y, want, atol=TOL)
    weighed = ref.routed_experts(p, x, z, ref._mm(None), bias_weighs=True)
    assert float(jnp.abs(weighed - want).max()) > 100 * TOL
