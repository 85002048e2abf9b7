"""Laguna at a tiny size on the CPU, against the plain reference
(benchmark/reference/laguna_ref.py: float32 at 'highest', attention a
head at a time under a mask over the whole sequence, a loop over the
experts), and the attention the model adds to ``ops/attention.py``: the
paged decode kernel over a ring (``window``), and a prompt's blocked
attention over grouped heads. Logits and cached rows are compared, never
sampled tokens.

Tolerances: everything here runs in float32 with 'highest' products
(tests/conftest.py), so two sides differ by the order of their sums only:
2e-5 absolute on logits of spread ~0.16 and on attention outputs of size
~1. A key one position outside the window moves an output by 1e-2 or
more."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna_glue as glue
from benchmark.reference import laguna_ref as ref
from ray_tpu.models.laguna import (FULL, SLIDING, LagunaAttention,
                                   LagunaConfig, LagunaModel, cache_spec)
from ray_tpu.models.mla import YarnRope
from ray_tpu.ops import attention as A

TOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = LagunaConfig.tiny()
    return cfg, glue.init_for(cfg, 11)


@pytest.mark.parametrize("S", [100, 300])
def test_full_forward_equals_the_reference(tiny, S):
    """Contexts of 3 and 9 windows (32 positions). 2 x 100 tokens take
    the routed layer's whole-row product, 2 x 300 its sorted one."""
    cfg, params = tiny
    ids = np.random.default_rng(1).integers(0, 512, (2, S))
    out = LagunaModel(cfg).apply(params, jnp.asarray(ids, jnp.int32))
    sizes = ref.sizes_of(cfg)
    for b in range(2):
        want = ref.forward(params["params"], ids[b], sizes)
        assert float(jnp.std(want)) > 0.05
        np.testing.assert_allclose(out[b], want, atol=TOL)
    # the control of the benchmark's check is another function: sliding
    # layers that see the whole context move the logits
    far = ref.forward(params["params"], ids[0], sizes, whole_context=True)
    assert float(jnp.max(jnp.abs(far - out[0]))) > 1e-2


def test_published_config_counts_the_published_parameters():
    """The form of ``gating`` is decided by the count of the whole model
    (ISSUE 37): a gate of one value a head gives 33.44 B (published
    33.4B)."""
    cfg = LagunaConfig()
    assert cfg.layer_types[:5] == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert cfg.num_attention_heads_per_layer[:5] == (48, 64, 64, 64, 48)
    assert cfg.mlp_layer_types[:2] == ("dense", "sparse")
    shapes = jax.eval_shape(
        LagunaModel(LagunaConfig(num_hidden_layers=5)).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))
    tables = count(shapes["embed"]) + count(shapes["lm_head"])
    assert tables == 2 * 100352 * 2048
    full_sparse, sliding = count(shapes["layers_4"]), count(shapes["layers_1"])
    dense = count(shapes["layers_0"])
    assert abs(count(shapes["layers_4"]["attn"]) - 29.46e6) < 0.01e6
    assert abs(count(shapes["layers_1"]["attn"]) - 37.88e6) < 0.01e6
    whole = tables + dense + 9 * full_sparse + 30 * sliding
    assert abs(whole / 1e9 - 33.44) < 0.01
    spec = cache_spec(LagunaConfig(num_hidden_layers=5))
    assert spec["expert_counts"] == (4, 256) and not spec["state"]
    assert {k: (p["layers"], p["row"], p.get("window"))
            for k, p in spec["pages"].items()} == {
        "k_full": (2, 1024, None), "v_full": (2, 1024, None),
        "k_window": (3, 1024, 512), "v_window": (3, 1024, 512)}


def test_yarn_ramp_attention_factor_and_the_untouched_half():
    """By hand, a full layer at the published settings: 64 rotated
    values, theta 500,000, 4,096 original positions. Pair i turns
    theta^(-2i/64) a position, so 4,096 positions hold 64 turns at i =
    64 ln(4096 / 128 pi) / (2 ln 500000) = 5.66 and one turn at i =
    64 ln(4096 / 2 pi) / (2 ln 500000) = 15.80: pairs 0..5 keep their
    frequency, pairs 16..31 have it divided by 64, pair 10 lies 5/11 of
    the way. cos and sin are multiplied by 0.1 ln 64 + 1 = 1.41589 on the
    rotated half; values 64..127 pass untouched. Pairs are halves:
    (x[i], x[i + 32])."""
    cfg = LagunaConfig()
    rope = cfg.rope_of(FULL)
    assert rope.blend == YarnRope(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    at = [64 * math.log(4096 / (t * 2 * math.pi)) / (2 * math.log(5e5))
          for t in (64, 1)]
    assert abs(at[0] - 5.66) < 5e-3 and abs(at[1] - 15.80) < 5e-3
    assert rope.blend.ramp_dims() == (5, 16)
    f = np.asarray(rope.blend.inv_freq(), np.float64)
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(f[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(f[16:], plain[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        f[10], plain[10] * (6 / 11 + 5 / 11 / 64), rtol=1e-6)
    assert abs(rope.attention_factor - (0.1 * math.log(64) + 1)) < 1e-12
    np.testing.assert_allclose(ref.rope_frequencies(
        64, dict(dict(cfg.rope_parameters)[FULL])), f, rtol=1e-6)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 2, 128)),
                    jnp.float32)
    pos = jnp.array([[0, 5, 9000]])
    y = np.asarray(rope.rotate(x, rope.cos_sin(pos)))
    np.testing.assert_array_equal(y[..., 64:], np.asarray(x)[..., 64:])
    m = rope.attention_factor
    np.testing.assert_allclose(y[0, 0, :, :64], np.asarray(x)[0, 0, :, :64]
                               * m, rtol=1e-6)
    angle = 5 * f[3]
    a, b = np.asarray(x)[0, 1, 0, 3], np.asarray(x)[0, 1, 0, 35]
    np.testing.assert_allclose(
        y[0, 1, 0, [3, 35]],
        [m * (a * math.cos(angle) - b * math.sin(angle)),
         m * (b * math.cos(angle) + a * math.sin(angle))], rtol=1e-5)
    # the reference's own rotation (positions 0, 1, 2) says the same
    np.testing.assert_allclose(
        np.asarray(ref.rotate(x[0], list(f), m)),
        np.asarray(rope.rotate(x, rope.cos_sin(jnp.array([[0, 1, 2]]))))[0],
        rtol=1e-5, atol=1e-6)
    # a sliding layer: plain rotary over all 128 values, no factor
    plain_rope = cfg.rope_of(SLIDING)
    assert plain_rope.attention_factor == 1.0
    np.testing.assert_allclose(
        np.asarray(plain_rope.blend.inv_freq()),
        10000.0 ** (-2.0 * np.arange(64) / 128), rtol=1e-6)


def test_the_windows_edge():
    """A sliding layer's token at p sees p - window + 1 .. p: moving the
    input at p - window moves nothing at p, moving it at p - window + 1
    does (float32, one attention layer alone)."""
    cfg = LagunaConfig.tiny()
    w = cfg.sliding_window
    attn = LagunaAttention(cfg, 8, w)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 80, 64)),
                    jnp.float32)
    params = attn.init(jax.random.PRNGKey(0), x)
    p = 70
    base = attn.apply(params, x)[0][0, p]
    outside = attn.apply(params, x.at[0, p - w].add(1.0))[0][0, p]
    inside = attn.apply(params, x.at[0, p - w + 1].add(1.0))[0][0, p]
    assert float(jnp.max(jnp.abs(outside - base))) == 0.0
    assert float(jnp.max(jnp.abs(inside - base))) > 1e-3
    full = LagunaAttention(cfg, 6, None)
    params = full.init(jax.random.PRNGKey(0), x)
    moved = full.apply(params, x.at[0, 0].add(1.0))[0][0, p]
    assert float(jnp.max(jnp.abs(moved - full.apply(params, x)[0][0, p]))) \
        > 1e-4


def _ring_pool(rng, B, ring, bs, C, lengths, window, runs=False):
    """Pools and ring tables holding, for each row, every position of
    its last ``window`` (and a few before: what a ring still holds) at
    the place the rule puts it; and the same rows laid out flat by
    position, for the masked reference. ``runs``: a row's ring is
    consecutive ascending pages (what the allocator hands out), else
    shuffled ones."""
    T = int(max(lengths))
    k_flat = rng.normal(size=(B, T, C)).astype(np.float32)
    v_flat = rng.normal(size=(B, T, C)).astype(np.float32)
    P = B * ring + 1
    k_pages = rng.normal(size=(2, P, bs, C)).astype(np.float32)  # stale rows
    v_pages = rng.normal(size=(2, P, bs, C)).astype(np.float32)
    tables = 1 + (np.arange if runs else rng.permutation)(
        B * ring).reshape(B, ring).astype(np.int32)
    for b, n in enumerate(lengths):
        for p in range(max(n - ring * bs + bs, 0), n):
            page = tables[b, (p // bs) % ring]
            k_pages[1, page, p % bs] = k_flat[b, p]
            v_pages[1, page, p % bs] = v_flat[b, p]
    return k_flat, v_flat, k_pages, v_pages, tables


@pytest.mark.parametrize("tables", ["shuffled", "one-run"])
@pytest.mark.parametrize("window", [64, 240])
@pytest.mark.parametrize("G", [6, 8])
def test_paged_decode_kernel_over_a_ring_equals_the_masked_gather(
        G, window, tables):
    """``paged_attention_decode(window=...)`` interpreted, groups of 6
    and 8 query heads a key/value head: rows shorter than the window,
    exactly a window, several rings long, and an empty row; over a ring
    of 5 pages (one group of 5) and of 16 (two groups of 8: a ring of one
    run is copied a group at once but where the walk wraps inside one),
    shuffled pages and one run a row."""
    rng = np.random.default_rng(G)
    Hkv, D, bs = 2, 128, 16
    ring, B = window // bs + 1, 5
    lengths = np.array([200, window, 17, 0, 333], np.int32)
    k_flat, v_flat, k_pages, v_pages, tables = _ring_pool(
        rng, B, ring, bs, Hkv * D, lengths, window, runs=tables == "one-run")
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), jnp.float32)
    args = (q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables), jnp.asarray(lengths))
    got = A.paged_attention_decode(*args, layer=1, window=window,
                                   interpret=True)
    gather = A.paged_attention_reference(*args, layer=1, window=window)
    # the mathematics, from the flat rows: a softmax over the window
    want = np.zeros((B, Hkv * G, D), np.float32)
    for b, n in enumerate(lengths):
        if n == 0:
            continue
        lo = max(n - window, 0)
        for h in range(Hkv * G):
            g = h // G
            k = k_flat[b, lo:n, g * D:(g + 1) * D]
            v = v_flat[b, lo:n, g * D:(g + 1) * D]
            s = (k @ np.asarray(q[b, h])) * D ** -0.5
            pr = np.exp(s - s.max())
            want[b, h] = (pr / pr.sum()) @ v
    np.testing.assert_allclose(gather, want, atol=TOL)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_paged_decode_kernel_without_a_window_is_what_it_was():
    """No ``window``: the kernel's jaxpr and its result are those of the
    call that never names one (bit-equal), and the ring's page rule does
    not enter."""
    rng = np.random.default_rng(2)
    B, H, Hkv, D, bs, NB = 3, 4, 2, 128, 16, 6
    k_pages = jnp.asarray(rng.normal(size=(1, 20, bs, Hkv * D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(1, 20, bs, Hkv * D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(19)[:B * NB].reshape(B, NB) + 1,
                         jnp.int32)
    lengths = jnp.asarray([90, 0, 33], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)

    def plain(*a):
        return A.paged_attention_decode(*a, interpret=True)

    def named(*a):
        return A.paged_attention_decode(*a, interpret=True, window=None)
    args = (q, k_pages, v_pages, tables, lengths)
    assert str(jax.make_jaxpr(plain)(*args)) \
        == str(jax.make_jaxpr(named)(*args))
    np.testing.assert_array_equal(plain(*args), named(*args))
    live = np.asarray(lengths) > 0      # (an empty row: zeros)
    np.testing.assert_allclose(
        plain(*args)[live], A.paged_attention_reference(*args)[live],
        atol=TOL)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("G", [1, 3])
def test_blocked_prompt_attention_equals_decode_attention_with_a_mask(
        window, G):
    """The prompt's kernel (``latent_prefill_attention``, interpreted:
    key blocks of 16 walked with a running softmax, grouped heads read
    where they lie, with ``window`` only the blocks a query block can
    see) and the plain form, against ``decode_attention`` over repeated
    heads with the window cut out of its context by hand. Two rows, one
    padded (positions -1)."""
    rng = np.random.default_rng(5)
    B, S, Hkv, d = 2, 64, 2, 16
    H = Hkv * G
    q = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, d)), jnp.float32)
    n = np.array([64, 37])
    pos = np.where(np.arange(S)[None, :] < n[:, None], np.arange(S), -1)
    pos = jnp.asarray(pos, jnp.int32)
    kernel = A.latent_prefill_attention(
        q, k, v, pos, d ** -0.5, block_q=16, block_k=16, window=window)
    plain = A.prefill_attention(q, k, v, pos, window=window)
    assert A.prefill_attention_path(q, k) == "plain"    # off the chip
    if window is None:
        want = A.decode_attention(
            q.transpose(0, 2, 1, 3), k, v, jnp.asarray(n), q_positions=pos
        ).transpose(0, 2, 1, 3)
    else:       # one query at a time over the window cut out by hand
        want = np.zeros((B, S, H, d), np.float32)
        for b in range(B):
            for p in range(n[b]):
                lo = max(p - window + 1, 0)
                out = A.decode_attention(
                    q[b:b + 1, p:p + 1].transpose(0, 2, 1, 3),
                    k[b:b + 1, lo:p + 1], v[b:b + 1, lo:p + 1],
                    jnp.asarray([p + 1 - lo]))
                want[b, p] = np.asarray(out)[0, :, 0]
    for b in range(B):
        np.testing.assert_allclose(kernel[b, :n[b]], want[b, :n[b]],
                                   atol=TOL)
        np.testing.assert_allclose(plain[b, :n[b]], want[b, :n[b]],
                                   atol=TOL)
    # a query block of padding alone visits no key block: zeros
    assert float(jnp.max(jnp.abs(kernel[1, 48:]))) == 0.0
