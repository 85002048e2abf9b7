"""Kimi-Linear at a tiny size on the CPU, against the plain reference
(benchmark/reference/kimi_linear_ref.py: float32 at 'highest', a scan
over tokens, explicit shifts, every head's keys and values built, a loop
over the experts held). Logits and layer outputs are compared, never
sampled tokens.

Tolerances: everything here runs in float32 with 'highest' products
(tests/conftest.py), so the two sides differ by the order of their sums
only. 2e-5 absolute on logits of spread ~0.16 is ~100 float32 roundings
through four layers; the chunked recurrence gets 2e-5 on outputs of
size <= 1 (a triangular solve of 64 unknowns against 64 sequential
updates). A bfloat16 run of the same sizes differs by ~1e-2: three
orders above either."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear_glue as glue
from benchmark.reference import kimi_linear_ref as ref
from ray_tpu.models.kimi_linear import (KimiLinearConfig, KimiLinearModel,
                                        MLAMixer, cache_spec)
from ray_tpu.ops import attention as A
from ray_tpu.ops import linear_attention as LA
from ray_tpu.parallel.moe import RoutedExperts, SwiGLU

TOL = 2e-5


def _scan_kda(q, k, v, g, beta, state):
    """The recurrence as written, a token at a time (one row)."""
    outs = []
    for t in range(q.shape[0]):
        s = np.exp(g[t])[:, :, None] * state
        r = v[t] - np.einsum("hkv,hk->hv", s, k[t])
        state = s + beta[t][:, None, None] * k[t][:, :, None] * r[:, None, :]
        outs.append(np.einsum("hkv,hk->hv", state, q[t]))
    return np.stack(outs), state


def _kda_inputs(S, seed, decay, H=2, d=16):
    """decay 'slow': log-decays of -1e-3 .. -3e-2 a token, so the first
    token still weighs ~0.05-0.9 after 100; 'fast': down to -6 a token
    (exp(-G) of a chunk would overflow float32: the chunked form must
    never take it); 'mixed': both in one head."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(S, H, d)) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * 4
    lo, hi = {"slow": (1e-3, 3e-2), "fast": (0.5, 6.0),
              "mixed": (1e-3, 6.0)}[decay]
    g = -np.exp(rng.uniform(np.log(lo), np.log(hi), (S, H, d)))
    beta = rng.uniform(0.05, 0.95, (S, H))
    state = rng.normal(size=(H, d, d)) * 0.3
    return [x.astype(np.float64) for x in (q, k, v, g, beta, state)]


@pytest.mark.parametrize("S,decay", [
    (64, "slow"), (128, "slow"), (37, "slow"), (150, "slow"),
    (100, "mixed"), (192, "fast")])
def test_chunked_kda_equals_the_scan(S, decay):
    q, k, v, g, beta, state = _kda_inputs(S, S, decay)
    want_o, want_s = _scan_kda(q, k, v, g, beta, state)
    f = lambda x: jnp.asarray(x, jnp.float32)[None]         # noqa: E731
    o, s = LA.kda_chunked(f(q), f(k), f(v), f(g), f(beta), f(state),
                          chunk=64)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o[0], want_o, atol=TOL)
    np.testing.assert_allclose(s[0], want_s, atol=TOL)
    if decay == "slow":     # history matters: a zero start changes it
        o0, _ = LA.kda_chunked(f(q), f(k), f(v), f(g), f(beta),
                               0 * f(state), chunk=64)
        assert float(jnp.abs(o0[0, -1] - o[0, -1]).max()) > 1e-3


def test_one_token_recurrence_and_empty_positions():
    q, k, v, g, beta, state = _kda_inputs(9, 5, "slow")
    want_o, want_s = _scan_kda(q, k, v, g, beta, state)
    f = lambda x: jnp.asarray(x, jnp.float32)               # noqa: E731
    s = f(state)[None]
    for t in range(9):
        o, s = LA.kda_recurrent_step(f(q[t])[None], f(k[t])[None],
                                     f(v[t])[None], f(g[t])[None],
                                     f(beta[t])[None], s)
        np.testing.assert_allclose(o[0], want_o[t], atol=TOL)
    np.testing.assert_allclose(s[0], want_s, atol=TOL)
    # g = 0, beta = 0 is "no token here": the state passes through
    _, same = LA.kda_recurrent_step(f(q[0])[None], f(k[0])[None],
                                    f(v[0])[None], 0 * f(g[0])[None],
                                    0 * f(beta[0])[None], s)
    np.testing.assert_array_equal(same, s)
    pad = lambda x: jnp.concatenate(                        # noqa: E731
        [f(x)[None], jnp.zeros((1, 70) + x.shape[1:], jnp.float32)], 1)
    _, s_pad = LA.kda_chunked(pad(q), pad(k), pad(v), pad(g), pad(beta),
                              f(state)[None])
    np.testing.assert_allclose(s_pad[0], want_s, atol=TOL)


# The decode kernel (``kda_recurrent_step_in_place``), interpreted. It is
# float32 throughout: the state's products are multiplied and summed on
# the vector unit in float32, never rounded to bfloat16 on the way, so it
# is held to the float64 scan at the tolerance of the XLA step (a 128-term
# float32 sum), and the same step with single-pass bfloat16 products must
# FAIL that tolerance: the benchmark's ``correct`` cannot see the
# difference (PERF.md section 7), this test can.
TILES = {"tiny": (2, 16), "published": (32, 128)}
# rows' slots in a pool of 6: out of order with the null slot twice (two
# padding rows), and rows in slot order
SLOTS = {"by_slots": (4, 0, 1, 0, 3), "in_slot_order": (1, 2, 3, 4, 5)}


def _pool_steps(tile, decay, slots, step, T=4):
    """``T`` tokens of len(slots) rows through ``step(q, k, v, g, beta,
    pool, layer, slots)`` on layer 1 of a pool [3, 6, H, d, d]; a row at
    slot 0 is a padding row (g = 0, beta = 0). Returns the largest
    differences to the float64 scan (outputs, final states), the first
    pool and the last."""
    H, d = TILES[tile]
    rows = [_kda_inputs(T, 7 * b + len(decay), decay, H, d)
            for b in range(len(slots))]
    rng = np.random.default_rng(3)
    first = rng.normal(size=(3, 6, H, d, d)).astype(np.float32) * 0.3
    for b, slot in enumerate(slots):
        if slot:
            first[1, slot] = rows[b][5]
        else:
            rows[b][3] *= 0.0
            rows[b][4] *= 0.0
    f = lambda i, t: jnp.asarray(                           # noqa: E731
        np.stack([r[i][t] for r in rows]), jnp.float32)
    pool, err_o = jnp.asarray(first), 0.0
    want = [_scan_kda(*r[:5], first[1, slot].astype(np.float64))
            for r, slot in zip(rows, slots)]
    for t in range(T):
        o, pool = step(f(0, t), f(1, t), f(2, t), f(3, t), f(4, t), pool,
                       1, jnp.asarray(slots, jnp.int32))
        for b, slot in enumerate(slots):
            if slot:
                err_o = max(err_o, float(np.abs(o[b] - want[b][0][t]).max()))
    err_s = max(float(np.abs(pool[1, slot] - w[1]).max())
                for w, slot in zip(want, slots) if slot)
    return err_o, err_s, first, np.asarray(pool)


def _xla_step(single_pass_bf16=False):
    """``kda_recurrent_step`` over gathered rows, scattered back; or its
    mathematics with the state's two products in ONE bfloat16 pass
    (what an MXU product at default precision would be)."""
    def step(q, k, v, g, beta, pool, layer, slots):
        state = pool[layer, slots]
        if not single_pass_bf16:
            o, new = LA.kda_recurrent_step(q, k, v, g, beta, state)
        else:
            bf = jnp.bfloat16
            a = jnp.exp(g)
            sk, sq = (jnp.einsum("bhij,bhi->bhj", state.astype(bf),
                                 (a * x).astype(bf),
                                 preferred_element_type=jnp.float32)
                      for x in (k, q))
            r = (v - sk) * beta[..., None]
            o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * r
            new = a[..., None] * state + k[..., None] * r[..., None, :]
        real = (slots > 0)[:, None, None, None]
        return o, pool.at[layer, slots].set(jnp.where(real, new, state))
    return step


_KERNEL = functools.partial(LA.kda_recurrent_step_in_place, interpret=True)


@pytest.mark.parametrize("slots", list(SLOTS))
@pytest.mark.parametrize("decay", ["slow", "mixed", "fast"])
@pytest.mark.parametrize("tile", list(TILES))
def test_decode_kernel_equals_the_step_and_the_scan(tile, decay, slots):
    """Float32 throughout: the kernel is as close to the float64 scan
    as the XLA step is, over several tokens, through a pool."""
    err_o, err_s, first, last = _pool_steps(tile, decay, SLOTS[slots],
                                            _KERNEL)
    assert err_o < TOL and err_s < TOL, (err_o, err_s)
    ref_o, ref_s, _, ref_last = _pool_steps(tile, decay, SLOTS[slots],
                                            _xla_step())
    assert ref_o < TOL and ref_s < TOL, (ref_o, ref_s)
    np.testing.assert_allclose(last, ref_last, atol=TOL)
    # slots the steps did not name, the null slot of the padding rows
    # and the other layers are bit for bit what they were
    named = np.zeros(first.shape[:2], bool)
    named[1, [s for s in SLOTS[slots] if s]] = True
    assert not named[1, 0]
    np.testing.assert_array_equal(last[~named], first[~named])
    assert np.abs(last[named] - first[named]).max() > 1e-3


@pytest.mark.parametrize("decay", ["slow", "mixed"])
def test_single_pass_bfloat16_products_fail_the_kernels_tolerance(decay):
    """The same inputs with the state's products rounded to bfloat16
    once: outside the tolerance the kernel passes, by an order and more.
    (Fast decays forget the state within a token, and hide it.)"""
    slots = SLOTS["by_slots"]
    err_o, err_s, _, _ = _pool_steps("published", decay, slots, _KERNEL)
    bf_o, bf_s, _, _ = _pool_steps("published", decay, slots,
                                   _xla_step(single_pass_bf16=True))
    assert max(err_o, err_s) < TOL
    assert bf_o > 10 * TOL and bf_s > 10 * TOL, (bf_o, bf_s)


def test_decode_kernel_leaves_an_empty_row_bit_for_bit():
    """``g = 0, beta = 0`` on a row that names a real slot (a free slot
    of the by-slot bucket): its state comes back bit for bit."""
    H, d = TILES["published"]
    q, k, v, g, beta, _ = (jnp.asarray(np.stack([x] * 2)[:, 0], jnp.float32)
                           for x in _kda_inputs(1, 2, "mixed", H, d))
    pool = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 3, H, d, d)), jnp.float32)
    live = jnp.array([1.0, 0.0])
    o, new = _KERNEL(q, k, v, g * live[:, None, None], beta * live[:, None],
                     pool, 0, jnp.array([2, 1]))
    np.testing.assert_array_equal(new[0, 1], pool[0, 1])
    np.testing.assert_array_equal(new[1], pool[1])
    np.testing.assert_array_equal(new[0, 0], pool[0, 0])
    assert float(jnp.abs(new[0, 2] - pool[0, 2]).max()) > 1e-3
    # and that row's output is the state's answer to its query
    np.testing.assert_allclose(o[1], jnp.einsum(
        "hij,hi->hj", pool[0, 1], q[1], precision="highest"), atol=TOL)


def _pool(H=32, d=128, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((6, 65, H, d, d), dtype)


@pytest.mark.parametrize("case,want", [
    ("chip", "kda_kernel"), ("cpu", "xla"), ("prompt", "xla"),
    ("no_pool", "xla"), ("mesh_2x2", "xla"), ("one_device_mesh",
                                              "kda_kernel"),
    ("head_size_16", "xla"), ("two_heads", "xla"), ("bfloat16", "xla")])
def test_kda_decode_path(monkeypatch, case, want):
    """The chooser, from what it can observe: the kernel for one token
    a row on a TPU over a float32 pool of whole tiles, outside a mesh
    of several devices; the XLA step for everything else."""
    from jax.sharding import Mesh
    monkeypatch.setattr(A, "_use_pallas", lambda: case != "cpu")
    pool = {"no_pool": None, "head_size_16": _pool(d=16),
            "two_heads": _pool(H=2),
            "bfloat16": _pool(dtype=jnp.bfloat16)}.get(case, _pool())
    devices = {"mesh_2x2": np.array(jax.devices()[:4]).reshape(2, 2),
               "one_device_mesh": np.array(jax.devices()[:1]).reshape(1, 1)}
    if case in devices:
        with A.attention_mesh(Mesh(devices[case], ("dp", "tp"))):
            got = LA.kda_decode_path(pool, 1)
    else:
        got = LA.kda_decode_path(pool, 64 if case == "prompt" else 1)
    assert got == want


@pytest.mark.parametrize("by_slot", [True, False],
                         ids=["in_slot_order", "by_slots"])
def test_served_decode_step_through_the_kernel(monkeypatch, by_slot):
    """The model's call site: a decode step of three rows (one a padding
    row) where the chooser says ``kda_kernel`` returns the logits, the
    state pool and the tails of the step that gathers and scatters."""
    cfg = KimiLinearConfig.tiny(kda_num_heads=8, kda_head_dim=128,
                                num_hidden_layers=3, kda_layers=(1, 2),
                                full_attn_layers=(3,))
    params = glue.init_for(cfg, 5)
    spec, rng = cache_spec(cfg), np.random.default_rng(6)
    B, n_slots = 3, 4
    cache = {"kv_pages": jnp.zeros((1, 4, 8, 128)),
             "block_tables": jnp.array([[1], [2], [0]]),
             "kda_state": jnp.asarray(rng.normal(size=(
                 2, n_slots, 8, 128, 128)) * 0.3, jnp.float32),
             "kda_conv": jnp.asarray(rng.normal(size=(
                 2, n_slots, spec["state"]["kda_conv"]["shape"][1])),
                 jnp.float32)}
    if not by_slot:
        cache["slots"] = jnp.array([3, 1, 0])
    kw = dict(cache=cache, seq_lengths=jnp.array([2, 5, 0]),
              valid=jnp.array([[True], [True], [False]]))
    ids = jnp.array([[7], [11], [0]])
    want, want_cache, _ = KimiLinearModel(cfg).apply(params, ids, **kw)
    monkeypatch.setattr(LA, "kda_decode_path",
                        lambda pool, S: "kda_kernel" if S == 1 else "xla")
    monkeypatch.setattr(LA, "kda_recurrent_step_in_place", _KERNEL)
    got, got_cache, _ = KimiLinearModel(cfg).apply(params, ids, **kw)
    np.testing.assert_allclose(got[:2], want[:2], atol=TOL)
    for name in ("kda_state", "kda_conv", "kv_pages"):
        np.testing.assert_allclose(got_cache[name], want_cache[name],
                                   atol=TOL)
    # the padding row's slot (slot 3 in slot order, the null slot
    # otherwise) and the slot no row names are bit for bit
    for slot in ((3,) if by_slot else (0, 2)):
        np.testing.assert_array_equal(got_cache["kda_state"][:, slot],
                                      cache["kda_state"][:, slot])
    assert B == got.shape[0]


def test_short_conv_carries_its_tail():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 20, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    zero = jnp.zeros((2, 3, 6))
    whole, _ = LA.short_conv(x, zero, w)
    # explicit shifts
    want = sum(np.pad(np.asarray(x), ((0, 0), (3 - j, 0), (0, 0)))[:, :20]
               * np.asarray(w[j]) for j in range(4))
    np.testing.assert_allclose(whole, want, atol=1e-6)
    # 11 real rows of a 16-row bucket, then the rest a row at a time
    first = jnp.concatenate([x[:, :11], jnp.full((2, 5, 6), 7.0)], 1)
    y, tail = LA.short_conv(first, zero, w, n_new=jnp.array([11, 11]))
    np.testing.assert_allclose(y[:, :11], whole[:, :11], atol=1e-6)
    np.testing.assert_array_equal(tail, x[:, 8:11])
    for t in range(11, 20):
        y, tail = LA.short_conv(x[:, t:t + 1], tail, w)
        np.testing.assert_allclose(y[:, 0], whole[:, t], atol=1e-6)
    # fewer real rows than the kernel: the old tail shifts, zeros stay
    _, tail = LA.short_conv(x[:, :8], zero, w, n_new=jnp.array([2, 0]))
    np.testing.assert_array_equal(tail[0], jnp.concatenate(
        [zero[0, :1], x[0, :2]]))
    np.testing.assert_array_equal(tail[1], zero[1])


@pytest.fixture(scope="module")
def tiny():
    cfg = KimiLinearConfig.tiny()
    return cfg, glue.init_for(cfg, 11)


@pytest.mark.parametrize("S", [100, 150])
def test_full_forward_equals_the_reference(tiny, S):
    """2 x 100 tokens take the routed layer's dense product, 2 x 150 its
    grouped products (more than moe.DENSE_BELOW)."""
    cfg, params = tiny
    ids = np.random.default_rng(1).integers(0, 512, (2, S))
    out = KimiLinearModel(cfg).apply(params, jnp.asarray(ids, jnp.int32))
    sizes = ref.sizes_of(cfg)
    for b in range(2):
        want = ref.forward(params["params"], ids[b], sizes)
        assert float(jnp.std(want)) > 0.05
        np.testing.assert_allclose(out[b], want, atol=TOL)


def test_the_weights_come_from_the_seed(tiny):
    cfg, params = tiny
    again = glue.init_for(cfg, 11)
    other = glue.init_for(cfg, 2**31 + 11)
    leaves = jax.tree_util.tree_leaves
    assert all(bool(jnp.array_equal(a, b))
               for a, b in zip(leaves(params), leaves(again)))
    assert not bool(jnp.array_equal(leaves(params)[0], leaves(other)[0]))
    kda = params["params"]["layers_0"]["kda"]
    # slow and fast decays are both there (32 channels here)
    g = -jnp.exp(kda["A_log"])[:, None] * jax.nn.softplus(
        kda["dt_bias"].reshape(cfg.kda_num_heads, -1))
    assert float(jnp.max(g)) > -0.05 and float(jnp.min(g)) < -0.5


def test_cache_spec_states_pages_and_state():
    cfg = KimiLinearConfig(num_hidden_layers=8, vocab_size=1024,
                           experts_held=(0, 64))
    spec = cache_spec(cfg)
    assert cfg.layer_kinds() == ("kda",) * 3 + ("mla",) + ("kda",) * 3 \
        + ("mla",)
    assert spec["pages"]["kv_pages"]["layers"] == 2
    assert spec["pages"]["kv_pages"]["row"] == 640   # 576 in whole lanes
    assert spec["state"]["kda_state"]["shape"] == (6, 32, 128, 128)
    assert spec["state"]["kda_state"]["dtype"] == jnp.float32
    assert spec["state"]["kda_conv"]["shape"] == (6, 3 * 3 * 4096)
    with pytest.raises(ValueError):
        KimiLinearConfig(num_hidden_layers=4, kda_layers=(1, 2),
                         full_attn_layers=(2, 3, 4))


def test_absorbed_mla_decode_equals_materialised():
    """One new token a row against 40 cached latents, rows of unequal
    length: the up-projection folded into query and output gives what
    every head's keys and values give."""
    rng = np.random.default_rng(2)
    B, T, H, R, dn, dr, dv = 3, 40, 2, 32, 16, 8, 16
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q_n, q_r = f(B, 1, H, dn), f(B, 1, H, dr)
    latent, w = f(B, T, R + dr), f(R, H, dn + dv) * 0.2
    pos = jnp.array([[39], [7], [-1]])
    a = A.latent_attention(q_n, q_r, latent, w, pos, v_dim=dv,
                           absorbed=True)
    m = A.latent_attention(q_n, q_r, latent, w, pos, v_dim=dv,
                           absorbed=False)
    np.testing.assert_allclose(a[:2], m[:2], atol=TOL)
    # and the materialised form is the textbook one, row 1: 8 keys
    kv = jnp.einsum("tr,rhd->thd", latent[1, :8, :R], w)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        latent[1, :8, None, R:], (8, H, dr))], -1)
    q = jnp.concatenate([q_n[1, 0], q_r[1, 0]], -1)
    p = jax.nn.softmax(jnp.einsum("hd,thd->ht", q, k) / np.sqrt(dn + dr), -1)
    np.testing.assert_allclose(
        m[1, 0], jnp.einsum("ht,thd->hd", p, kv[..., dn:]), atol=TOL)
    # queries in blocks of 8 give what one block gives
    qs_n, qs_r = f(1, 32, H, dn), f(1, 32, H, dr)
    pos = jnp.arange(32)[None] + 8
    np.testing.assert_allclose(
        A.latent_attention(qs_n, qs_r, latent[:1], w, pos, v_dim=dv,
                           q_block=8),
        A.latent_attention(qs_n, qs_r, latent[:1], w, pos, v_dim=dv),
        atol=TOL)


def _layer(held):
    return RoutedExperts(16, 32, 4, held=held, scaling=2.446,
                         shared_d_ff=32, dtype=jnp.float32)


def _ref_layer(p, x, held):
    z = {"held": held, "top_k": 4, "scaling": 2.446, "renormalize": True}
    with jax.default_matmul_precision("highest"):
        return ref.routed_experts(p, x, z, ref._mm(None))


@pytest.mark.parametrize("T", [200, 400])
def test_routed_layer_drops_nothing_under_a_forced_skew(T):
    """The correction bias sends every token to expert 1 (and most to
    expert 2): 200 tokens on one expert of 4 held, where a capacity of
    1.25 x 200 x 4 / 16 = 62 would drop 138. Equal to the dense loop,
    in the dense product (200 tokens) and in the grouped ones (400, more
    than moe.DENSE_BELOW: one expert's group then spans two blocks)."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, 24)),
                    jnp.float32)
    layer = _layer((0, 4))
    params = layer.init(jax.random.PRNGKey(0), x)
    bias = jnp.zeros((16,)).at[1].set(10.0).at[2].set(0.1)
    params = {"params": dict(params["params"], router_bias=bias)}
    real = T - 10
    valid = jnp.arange(T) < real
    y, counts = layer.apply(params, x, valid=valid)
    assert int(counts[1]) == real and int(counts[2]) > 0.3 * T
    assert int(counts.sum()) == int(jnp.sum(
        (jax.lax.top_k(jax.nn.sigmoid(x @ params["params"]["router"])
                       + bias, 4)[1] < 4)[:real]))
    want = _ref_layer(params["params"], x, (0, 4))
    np.testing.assert_allclose(y[:real], want[:real], atol=TOL)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """16 experts four ways: what each share's own experts give, with
    the shared expert (which every chip computes alike) counted once,
    is the whole layer, as the reference computes it uncut."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 24)),
                    jnp.float32)
    whole = _layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    y_whole, counts_whole = whole.apply({"params": params}, x)
    shared = SwiGLU(32, jnp.float32).apply({"params": params["shared"]}, x)
    total, touched = shared, 0
    for first in (0, 4, 8, 12):
        p = dict(params, **{k: params[k][first:first + 4]
                            for k in ("w_gate", "w_up", "w_down")})
        y, counts = _layer((first, 4)).apply({"params": p}, x)
        np.testing.assert_array_equal(counts,
                                      counts_whole[first:first + 4])
        total = total + (y - shared)
        touched += int(counts.sum())
    assert touched == 50 * 4
    np.testing.assert_allclose(total, y_whole, atol=TOL)
    np.testing.assert_allclose(y_whole, _ref_layer(params, x, (0, 16)),
                               atol=TOL)


def test_mla_mixer_through_pages_equals_its_own_full_pass():
    """Prefill 21 tokens into pages, then 5 more one at a time
    (absorbed): each new row equals the cache-free pass's row."""
    cfg = KimiLinearConfig.tiny()
    mixer = MLAMixer(cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 26, 64)),
                    jnp.float32)
    params = mixer.init(jax.random.PRNGKey(2), x)
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
