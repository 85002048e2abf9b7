"""Kimi-K2 at a tiny size on the CPU, against the plain reference
(benchmark/reference/kimi_k2_ref.py: float32 at 'highest', attention a
head at a time with every head's keys and values built, a loop over the
experts held). Logits, layer outputs and cached rows are compared, never
sampled tokens.

Tolerances: everything here runs in float32 with 'highest' products
(tests/conftest.py), so the two sides differ by the order of their sums
only: 2e-5 absolute on logits of spread ~0.16 and on cached rows of size
~1. A rotation at a position one off moves a cached row by ~0.1."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_k2_glue as glue
from benchmark.reference import kimi_k2_ref as ref
from ray_tpu.models.kimi_k2 import KimiK2Config, KimiK2Model, cache_spec
from ray_tpu.models.mla import MLAMixer, YarnRope
from ray_tpu.ops import attention as A
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import RoutedExperts, SwiGLU

TOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = KimiK2Config.tiny()
    return cfg, glue.init_for(cfg, 11)


@pytest.mark.parametrize("S", [100, 300])
def test_full_forward_equals_the_reference(tiny, S):
    """2 x 100 tokens take the routed layer's whole-row product, 2 x 300
    its sorted one (more than moe.WHOLE_ROWS_BELOW)."""
    cfg, params = tiny
    ids = np.random.default_rng(1).integers(0, 512, (2, S))
    out = KimiK2Model(cfg).apply(params, jnp.asarray(ids, jnp.int32))
    sizes = ref.sizes_of(cfg)
    for b in range(2):
        want = ref.forward(params["params"], ids[b], sizes)
        assert float(jnp.std(want)) > 0.05
        np.testing.assert_allclose(out[b], want, atol=TOL)


def test_yarn_frequencies_and_mscale_at_the_published_settings():
    """By hand: 64 values, theta 50,000, 4,096 original positions. A pair
    i turns theta^(-2i/64) a position, so 4,096 positions hold 32 turns at
    i = 64 ln(4096 / 64 pi) / (2 ln 50000) = 8.91 and one turn at i =
    64 ln(4096 / 2 pi) / (2 ln 50000) = 19.16: pairs 0..8 keep their
    frequency, pairs 20..31 have it divided by 64, pair 14 is half way
    ((14 - 8) / 12). m = 0.1 ln 64 + 1."""
    cfg = KimiK2Config()
    rope = cfg.rope
    assert rope == YarnRope(64, 50000.0, 64, 4096, 32, 1, 1, 1)
    assert rope.ramp_dims() == (8, 20)
    f = np.asarray(rope.inv_freq(), np.float64)
    plain = 50000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(f[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(f[20:], plain[20:] / 64, rtol=1e-6)
    np.testing.assert_allclose(f[14], plain[14] * (0.5 + 0.5 / 64),
                               rtol=1e-6)
    assert abs(f[1] - 0.7131) < 1e-4            # 50000^(-1/32)
    assert abs(f[31] * 64 - 2.8045e-5) < 1e-8   # 50000^(-31/32)
    assert abs(rope.softmax_mscale - 1.415888) < 1e-6
    assert rope.cos_sin_scale == 1.0
    # the reference's own arithmetic says the same
    np.testing.assert_allclose(
        ref.yarn_frequencies(64, 50000.0, cfg.rope_scaling), f, rtol=1e-6)
    assert abs(ref.softmax_mscale(cfg.rope_scaling)
               - (0.1 * math.log(64) + 1)) < 1e-12
    # a rotation keeps lengths and turns pair (x0, x1) by pos * f
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 2, 64)),
                    jnp.float32)
    pos = jnp.array([[0, 5, 9000]])
    y = rope.rotate(x, rope.cos_sin(pos))
    np.testing.assert_allclose(y[0, 0], x[0, 0], atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    a = 5 * f[1]
    np.testing.assert_allclose(
        y[0, 1, 0, 2:4], [x[0, 1, 0, 2] * np.cos(a) - x[0, 1, 0, 3] * np.sin(a),
                          x[0, 1, 0, 2] * np.sin(a) + x[0, 1, 0, 3] * np.cos(a)],
        atol=1e-5)


def test_cache_spec_states_one_pool_and_no_state():
    cfg = KimiK2Config(num_hidden_layers=7, vocab_size=1024,
                       experts_held=(0, 12))
    spec = cache_spec(cfg)
    assert spec["pages"]["kv_pages"] == {"layers": 7, "row": 640,
                                         "latent_rank": 512,
                                         "dtype": jnp.bfloat16}
    assert spec["state"] == {} and spec["expert_counts"] == (6, 12)
    hash(cfg)       # flax wants a module's attributes hashable


def _rows(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def test_absorbed_equals_materialised_with_a_rotated_key_part():
    """One new token a row against 40 cached rows whose key part was
    rotated at its position when written, rows of unequal length: the
    up-projection folded into query and output gives what every head's
    keys and values give, under the YaRN softmax scale."""
    rng = np.random.default_rng(2)
    B, T, H, R, dn, dr, dv = 3, 40, 2, 32, 16, 8, 16
    rope = KimiK2Config.tiny().rope
    at = rope.cos_sin(jnp.broadcast_to(jnp.arange(T)[None], (B, T)))
    latent = jnp.concatenate([
        _rows(rng, B, T, R), rope.rotate(_rows(rng, B, T, dr), at)], -1)
    pos = jnp.array([[39], [7], [-1]])
    q_n = _rows(rng, B, 1, H, dn)
    q_r = rope.rotate(_rows(rng, B, 1, H, dr),
                      rope.cos_sin(jnp.maximum(pos, 0)))
    w = _rows(rng, R, H, dn + dv) * 0.2
    scale = (dn + dr) ** -0.5 * rope.softmax_mscale ** 2
    a, m = (A.latent_attention(q_n, q_r, latent, w, pos, v_dim=dv,
                               absorbed=absorbed, sm_scale=scale)
            for absorbed in (True, False))
    np.testing.assert_allclose(a[:2], m[:2], atol=TOL)
    # and the scale is what is used: the default one gives another row
    plain = A.latent_attention(q_n, q_r, latent, w, pos, v_dim=dv)
    assert float(jnp.abs(plain[0] - m[0]).max()) > 1e-3


@pytest.mark.parametrize("S", [512, 1024])
def test_keys_in_blocks_with_a_running_softmax_change_nothing(monkeypatch,
                                                              S):
    """S queries over 640 positions (one query block, and two): where a
    block's logits over the whole context would pass the budget, one
    kernel walks the keys 128 at a time with a running softmax (here
    interpreted), up to the last block a query block can see. The same
    rows as one softmax gives; a row that sees a single key returns that
    key's value; a block of nothing but padding returns zeros."""
    rng = np.random.default_rng(3)
    H, R, dn, dr, dv = 2, 32, 16, 8, 16
    pos = jnp.concatenate([jnp.arange(S - 40) + 60, -jnp.ones(40, int)])
    pos = pos.at[3].set(0)[None]
    args = (_rows(rng, 1, S, H, dn), _rows(rng, 1, S, H, dr),
            _rows(rng, 1, 640, R + dr), _rows(rng, R, H, dn + dv) * 0.2,
            pos)
    whole = A.latent_attention(*args, v_dim=dv)
    calls, kernel = [], A.latent_prefill_attention
    monkeypatch.setattr(A, "latent_prefill_attention",
                        lambda *a: calls.append(a[0].shape) or kernel(*a))
    assert not calls
    monkeypatch.setattr(A, "LATENT_LOGITS_BYTES", 1 << 20)
    got = A.latent_attention(*args, v_dim=dv)
    assert calls == [(1, S, H, dn + dr)]
    np.testing.assert_allclose(got[0, :S - 40], whole[0, :S - 40], atol=TOL)
    value0 = jnp.einsum("r,rhd->hd", args[2][0, 0, :R], args[3][..., dn:])
    np.testing.assert_allclose(got[0, 3], value0, atol=TOL)
    assert bool(jnp.isfinite(got).all())
    padding = A.latent_attention(*args[:4], -jnp.ones((1, S), int),
                                 v_dim=dv)
    assert float(jnp.abs(padding).max()) == 0.0


def test_mixer_through_pages_equals_its_own_full_pass():
    """Prefill 21 tokens into pages, then 5 more one at a time
    (absorbed, each rotated at its own position): each new row equals the
    cache-free pass's row."""
    cfg = KimiK2Config.tiny()
    mixer = MLAMixer(cfg)
    x = _rows(np.random.default_rng(5), 1, 26, 64)
    params = mixer.init(jax.random.PRNGKey(2), x)
    assert {"q_a", "q_norm", "q_b"} <= set(params["params"]) \
        and "q_proj" not in params["params"]
    full, _ = mixer.apply(params, x)
    pages = jnp.zeros((1, 5, 8, 40))
    tables = jnp.array([[3, 1, 4, 2]])
    valid = (jnp.arange(32) < 21)[None]
    pre = jnp.pad(x[:, :21], ((0, 0), (0, 11), (0, 0)))
    y, pages = mixer.apply(params, pre, pages, tables, jnp.array([0]),
                           valid, 0)
    np.testing.assert_allclose(y[:, :21], full[:, :21], atol=TOL)
    for t in range(21, 26):
        y, pages = mixer.apply(params, x[:, t:t + 1], pages, tables,
                               jnp.array([t]), None, 0)
        np.testing.assert_allclose(y[:, 0], full[:, t], atol=TOL)
    assert float(jnp.abs(pages[0, 0]).max()) == 0.0   # null page untouched


def test_rows_written_after_a_nonzero_start_are_the_references(tiny):
    """A prompt of 37 tokens goes into the pool as 13 and then 24 (the
    second call starts at length 13, as a suffix after a shared prefix
    does): every layer's rows 0..36 are the reference's (c, RoPE(k_r))
    at their absolute positions; rotated from zero again, rows 13..36
    would be off by far more than the tolerance."""
    cfg, params = tiny
    ids = np.random.default_rng(7).integers(0, 512, 37)
    model = KimiK2Model(cfg)
    pages = jnp.zeros((cfg.num_hidden_layers, 9, 8, 128), jnp.float32)
    table = jnp.array([[5, 2, 7, 1, 3]])
    cache = {"kv_pages": pages, "block_tables": table}
    for start, stop, bucket in ((0, 13, 16), (13, 37, 32)):
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :stop - start] = ids[start:stop]
        valid = (jnp.arange(bucket) < stop - start)[None]
        _, cache, _ = model.apply(
            params, jnp.asarray(tokens), cache=cache,
            seq_lengths=jnp.array([start]), valid=valid)
    got = jnp.stack([A.paged_gather(cache["kv_pages"], table, layer)[0]
                     for layer in range(cfg.num_hidden_layers)])
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    _, want = ref.forward(params["params"], ids, ref.sizes_of(cfg),
                          latents_at=np.arange(37))
    np.testing.assert_allclose(got[:, :37, :width], want, atol=TOL)
    assert float(jnp.abs(got[:, :37, width:]).max()) == 0.0   # the lanes
    # what a rotation from zero at the second call would have written
    _, restarted = ref.forward(params["params"], ids[13:],
                               ref.sizes_of(cfg), latents_at=np.arange(24))
    off = jnp.abs(restarted[0, :, cfg.kv_lora_rank:]
                  - want[0, 13:, cfg.kv_lora_rank:]).max()
    assert float(off) > 1000 * TOL


def _layer(held, experts=384, top_k=8, d_ff=8):
    return RoutedExperts(experts, d_ff, top_k, held=held, scaling=2.827,
                         shared_d_ff=d_ff, dtype=jnp.float32)


def _ref_layer(p, x, held, top_k=8):
    z = {"held": held, "top_k": top_k, "scaling": 2.827,
         "renormalize": True}
    with jax.default_matmul_precision("highest"):
        return ref.routed_experts(p, x, z, ref._mm(None))


def test_the_32_shares_add_up_to_the_uncut_layer():
    """384 experts 32 ways, 12 a share, 8 a token: what each share's own
    experts give, with the shared expert (which every chip computes
    alike) counted once, is the whole layer, as the reference computes it
    uncut."""
    x = _rows(np.random.default_rng(4), 40, 24)
    whole = _layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    y_whole, counts_whole = whole.apply({"params": params}, x)
    shared = SwiGLU(8, jnp.float32).apply({"params": params["shared"]}, x)
    total, touched = shared, 0
    for first in range(0, 384, 12):
        p = dict(params, **{k: params[k][first:first + 12]
                            for k in ("w_gate", "w_up", "w_down")})
        y, counts = _layer((first, 12)).apply({"params": p}, x)
        np.testing.assert_array_equal(counts,
                                      counts_whole[first:first + 12])
        total = total + (y - shared)
        touched += int(counts.sum())
    assert touched == 40 * 8
    np.testing.assert_allclose(total, y_whole, atol=TOL)
    np.testing.assert_allclose(y_whole, _ref_layer(params, x, (0, 384)),
                               atol=TOL)


@pytest.mark.parametrize("chunk_bytes", [None, 100_000, 1],
                         ids=["whole", "chunks_of_2_blocks",
                              "chunks_of_1_block"])
def test_the_grouped_product_a_chunk_at_a_time_equals_the_reference(
        monkeypatch, chunk_bytes):
    """2,048 tokens, 4 of 16 experts held: whether the sorted rows are
    held whole (the default budget holds these: one gather, one kernel
    call, each token gathers its results back) or a chunk of two blocks
    or of one at a time (the loop over the live chunks that an 8,192-token
    prompt at Kimi-K2's width takes: the budget set under these rows),
    the layer gives what the reference's dense loop gives, padding
    tokens left out, and the kernel is traced once a layer whatever the
    chunks."""
    x = _rows(np.random.default_rng(6), 2048, 24)
    layer = _layer((0, 4), experts=16, top_k=4, d_ff=32)
    params = layer.init(jax.random.PRNGKey(0), x)
    valid = jnp.arange(2048) < 2000
    want = _ref_layer(params["params"], x, (0, 4), top_k=4)
    worst = 2048 * 4 + 4 * 256
    if chunk_bytes:
        monkeypatch.setattr(moe, "ROWS_BYTES", worst * 24 * 8 - 1)
        monkeypatch.setattr(moe, "CHUNK_BYTES", chunk_bytes)
    plan = moe.expert_product(2048, 4, 16, 4, 24, 4)
    assert plan == ("grouped_kernel", 256,
                    {None: worst, 100_000: 512, 1: 256}[chunk_bytes])
    calls = []
    fn = moe.grouped_experts
    monkeypatch.setattr(moe, "grouped_experts",
                        lambda *a: calls.append(a[0].shape) or fn(*a))
    y, counts = layer.apply(params, x, valid=valid)
    # traced once: the chunks are a loop's steps, not unrolled Python
    assert calls == [(plan.chunk_rows, 24)]
    np.testing.assert_allclose(y[:2000], want[:2000], atol=TOL)
    assert int(counts.sum()) > 256 * 4      # more live rows than a chunk's
    assert plan.rows_multiplied(counts) >= int(counts.sum())


def test_the_rows_held_at_a_time_at_the_cells_shapes():
    """What the grouped product holds of the sorted rows at the three
    cells' shapes (bfloat16 rows in, float32 results out): the worst
    case's (every assignment landing here) where they fit ``ROWS_BYTES``
    (Laguna's 1.5 GiB, Kimi-Linear's 0.32), a chunk of at most
    ``CHUNK_BYTES`` where they do not (2.75 GiB at Kimi-K2's width, which
    the compiler refused in float32 alone, PR 35)."""
    gib = 2.0 ** 30
    for T, k, experts, held, d, worst_gib in (
            (8192, 8, 384, 12, 7168, 2.75),     # Kimi-K2
            (8192, 8, 256, 256, 2048, 1.5),     # Laguna
            (2048, 8, 256, 64, 2304, 0.32)):    # Kimi-Linear
        plan = moe.expert_product(T, k, experts, held, d)
        worst = (T * k + held * plan.block_rows) * d * 6
        assert abs(worst / gib - worst_gib) < 0.01
        if worst > moe.ROWS_BYTES:
            assert plan.chunk_rows * d * 6 <= moe.CHUNK_BYTES
        else:
            assert plan.chunk_rows * d * 6 == worst
