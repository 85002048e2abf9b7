"""Jamba at a tiny size on the CPU, against the plain reference
(benchmark/reference/jamba_ref.py: float32 at 'highest', the Mamba-1
recurrence a scan over single tokens on a state [d_in, N], explicit
shifts, whole-sequence attention over the one key/value head). Logits and
layer outputs are compared, never sampled tokens.

Tolerances: everything here runs in float32 with 'highest' products
(tests/conftest.py), so the two sides differ by the order of their sums
only. 5e-5 absolute on logits of spread ~0.16 and on recurrence outputs
of size ~5. A single bfloat16 rounding of the state moves an output by
~1e-2: two orders above."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba_glue as glue
from benchmark.reference import jamba_ref as ref
from ray_tpu.models.jamba import JambaConfig, JambaModel, cache_spec
from ray_tpu.ops import attention as A
from ray_tpu.ops import linear_attention as LA
from ray_tpu.ops import ssm

TOL = 5e-5


# ------------------------------------------------------- the recurrence

def _inputs(R, S, seed, d_in=256, N=16, lengths=None):
    """Mamba-1 inputs: ``dt`` log-uniform 1e-3 .. 0.5 (decays of exp(-8)
    .. exp(-1e-3) a token with A in -1 .. -16), zero past a row's
    length."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        (R, S, d_in))), jnp.float32)
    if lengths is not None:
        valid = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
    A_ = -jnp.asarray(rng.uniform(1, 16, (N, d_in)), jnp.float32)
    D = jnp.asarray(rng.uniform(0.5, 1.5, (d_in,)), jnp.float32)
    return f(R, S, d_in), dt, f(R, S, N), f(R, S, N), A_, D, \
        0.3 * f(R, N, d_in)


def _token_scan(u, dt, B, C, A_, D, state):
    """``mamba_step`` a token at a time."""
    ys = []
    for t in range(u.shape[1]):
        y, state = ssm.mamba_step(u[:, t], dt[:, t], B[:, t], C[:, t], A_,
                                  D, state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


def test_the_step_is_the_equation_as_written():
    u, dt, B, C, A_, D, state = (np.asarray(t, np.float64)
                                 for t in _inputs(1, 1, 0))
    y, new = ssm.mamba_step(*(jnp.asarray(t[:, 0], jnp.float32)
                              for t in (u, dt, B, C)), A_, D,
                            jnp.asarray(state, jnp.float32))
    # h_c,n <- exp(dt_c A_c,n) h_c,n + dt_c B_n u_c, held [n, c]
    h = np.exp(dt[0, 0][None, :] * A_) * state[0] \
        + np.outer(B[0, 0], dt[0, 0] * u[0, 0])
    np.testing.assert_allclose(new[0], h, atol=TOL)
    np.testing.assert_allclose(y[0], C[0, 0] @ h + D * u[0, 0], atol=TOL)


@pytest.mark.parametrize("zero_state", [True, False])
@pytest.mark.parametrize("S,chunk", [(16, 8), (37, 8), (5, 8), (24, 1)])
def test_the_scan_equals_the_token_by_token_recurrence(S, chunk, zero_state):
    """Whole chunks and not, a sequence shorter than a chunk, from a zero
    and a non-zero state, one row right-padded (``dt`` 0)."""
    u, dt, B, C, A_, D, state = _inputs(2, S, S, lengths=(S, S - 3))
    if zero_state:
        state = 0 * state
    want_y, want_s = _token_scan(u, dt, B, C, A_, D, state)
    y, s = ssm.mamba_scan(u, dt, B, C, A_, D, state, chunk=chunk)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=TOL, rtol=1e-5)
    # the padded positions left the second row's state where token S - 4
    # put it; a row of padding alone leaves its state bit for bit
    _, s_short = ssm.mamba_scan(*(t[1:, :S - 3] for t in (u, dt, B, C)),
                                A_, D, state[1:], chunk=chunk)
    np.testing.assert_allclose(s[1:], s_short, atol=TOL)
    _, s_none = ssm.mamba_scan(u, 0 * dt, B, C, A_, D, state, chunk=chunk)
    np.testing.assert_array_equal(s_none, state)
    if not zero_state:      # history matters
        y0, _ = ssm.mamba_scan(u, dt, B, C, A_, D, 0 * state, chunk=chunk)
        assert float(jnp.abs(y0[:, 0] - y[:, 0]).max()) > 1e-3


# channels of the kernel's tile: the published 5,120 (five passes of 1,024
# lanes), one pass, and a width that only 128 lanes divide
TILES = {"published": 5120, "one_pass": 256, "odd": 384}
# rows of one block of u / dt / y: fewer than a block's 8, and two blocks
SLOTS = {"in_order": (1, 2, 3), "by_slots": (4, 2, 5), "padded": (3, 0, 1),
         "two_blocks": tuple(range(16, 0, -1))}
_KERNEL = functools.partial(ssm.mamba_step_in_place, interpret=True)


def _xla_step(round_state=None):
    """``mamba_step`` over gathered rows, scattered back; or with the
    state rounded once (to bfloat16) before the update."""
    def step(u, dt, B, C, A_, D, pool, layer, slots):
        state = pool[layer, slots]
        seen = state if round_state is None \
            else state.astype(round_state).astype(jnp.float32)
        y, new = ssm.mamba_step(u, dt, B, C, A_, D, seen)
        real = (slots > 0)[:, None, None]
        return y, pool.at[layer, slots].set(jnp.where(real, new, state))
    return step


def _pool_steps(tile, slots, step, T=5):
    """``T`` tokens through layer 1 of a pool [3, 17, N, d_in], a row a
    slot (slot 0: a padding row, ``dt = 0``). Returns the largest error
    of an output and of a final state against the token scan, the pool
    before and after."""
    d_in = TILES[tile]
    u, dt, B, C, A_, D, _ = _inputs(len(slots), T, len(tile), d_in)
    dt = dt * (jnp.asarray(slots) > 0)[:, None, None]
    first = 0.3 * jnp.asarray(np.random.default_rng(3).standard_normal(
        (3, 17, 16, d_in)), jnp.float32)
    at = jnp.asarray(slots, jnp.int32)
    want_y, want_s = _token_scan(u, dt, B, C, A_, D, first[1, at])
    pool, err_y = first, 0.0
    live = np.asarray(slots) > 0
    for t in range(T):
        y, pool = step(u[:, t], dt[:, t], B[:, t], C[:, t], A_, D, pool,
                       1, at)
        err_y = max(err_y, float(np.abs(y - want_y[:, t])[live].max()))
    err_s = float(np.abs(pool[1, at] - want_s)[live].max())
    return err_y, err_s, np.asarray(first), np.asarray(pool)


@pytest.mark.parametrize("slots", list(SLOTS))
@pytest.mark.parametrize("tile", list(TILES))
def test_decode_kernel_equals_the_step(tile, slots):
    """The kernel (interpreted) against ``mamba_step``, rows in slot
    order and by ``slots``, through a pool over several tokens."""
    err_y, err_s, first, last = _pool_steps(tile, SLOTS[slots], _KERNEL)
    assert err_y < TOL and err_s < TOL, (err_y, err_s)
    _, _, _, ref_last = _pool_steps(tile, SLOTS[slots], _xla_step())
    np.testing.assert_allclose(last, ref_last, atol=TOL)
    # slots the steps did not name, the null slot of the padding rows
    # and the other layers are bit for bit what they were
    named = np.zeros(first.shape[:2], bool)
    named[1, [s for s in SLOTS[slots] if s]] = True
    np.testing.assert_array_equal(last[~named], first[~named])
    assert np.abs(last[named] - first[named]).max() > 1e-3


def test_a_bfloat16_state_fails_the_kernels_tolerance():
    """The same inputs with the state rounded to bfloat16 once a token:
    outside the tolerance the kernel passes, by an order and more."""
    err_y, err_s, _, _ = _pool_steps("published", SLOTS["by_slots"],
                                     _KERNEL)
    bf_y, bf_s, _, _ = _pool_steps("published", SLOTS["by_slots"],
                                   _xla_step(jnp.bfloat16))
    assert max(err_y, err_s) < TOL
    assert bf_y > 10 * TOL and bf_s > 10 * TOL, (bf_y, bf_s)


def test_decode_step_takes_the_pool_by_row_or_by_slot(monkeypatch):
    """``mamba_decode_step`` without ``slots``: row r is slot r + 1. With
    the chooser patched to the kernel it gives what the XLA path gives."""
    u, dt, B, C, A_, D, _ = _inputs(3, 1, 11)
    pool = 0.3 * jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 4, 16, 256)), jnp.float32)
    one = (u[:, 0], dt[:, 0], B[:, 0], C[:, 0], A_, D)
    y0, p0 = ssm.mamba_decode_step(*one, pool, 1)
    y1, p1 = ssm.mamba_decode_step(*one, pool, 1, jnp.array([1, 2, 3]))
    np.testing.assert_array_equal(p0, p1)
    np.testing.assert_array_equal(p0[0], pool[0])
    np.testing.assert_array_equal(p0[1, 0], pool[1, 0])
    monkeypatch.setattr(ssm, "mamba_decode_path",
                        lambda pool, S: "mamba_kernel")
    monkeypatch.setattr(ssm, "mamba_step_in_place", _KERNEL)
    y2, p2 = ssm.mamba_decode_step(*one, pool, 1)
    np.testing.assert_allclose(y2, y0, atol=TOL)
    np.testing.assert_allclose(p2, p0, atol=TOL)


@pytest.mark.parametrize("case,want", [
    ("chip", "mamba_kernel"), ("cpu", "xla"), ("prompt", "xla"),
    ("bfloat16 pool", "xla"), ("narrow channels", "xla"), ("mesh", "xla")])
def test_mamba_decode_path(monkeypatch, case, want):
    import types
    monkeypatch.setattr(A, "_use_pallas", lambda: case != "cpu")
    pool = jax.ShapeDtypeStruct(
        (26, 257, 16, 64 if case == "narrow channels" else 5120),
        jnp.bfloat16 if case == "bfloat16 pool" else jnp.float32)
    if case == "mesh":
        monkeypatch.setattr(A._TRACE_MESH, "mesh",
                            types.SimpleNamespace(size=4), raising=False)
    assert ssm.mamba_decode_path(pool, 8 if case == "prompt" else 1) == want


def test_short_conv_adds_its_bias_and_carries_its_tail():
    """With a bias: every output row gains it, the tail does not; two
    halves with the carried tail equal the whole."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((6,)), jnp.float32)
    zero = jnp.zeros((2, 3, 6))
    plain, tail = LA.short_conv(x, zero, w)
    biased, tail_b = LA.short_conv(x, zero, w, bias=b)
    np.testing.assert_allclose(biased, plain + b, atol=1e-6)
    np.testing.assert_array_equal(tail, tail_b)
    first, t1 = LA.short_conv(x[:, :5], zero, w, bias=b)
    second, t2 = LA.short_conv(x[:, 5:], t1, w, bias=b)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), biased,
                               atol=1e-6)
    np.testing.assert_array_equal(t2, tail)


# ------------------------------------------------------------ the model

@pytest.fixture(scope="module")
def tiny():
    cfg = JambaConfig.tiny()
    return cfg, glue.init_for(cfg, 3000000019), ref.sizes_of(cfg)


@pytest.mark.parametrize("S", [40, 100])
def test_full_forward_equals_the_reference(tiny, S):
    cfg, params, sizes = tiny
    ids = np.random.default_rng(S).integers(0, cfg.vocab_size, (2, S))
    got = JambaModel(cfg).apply(params, jnp.asarray(ids, jnp.int32))
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
    m = params["params"]["mamba_1"]["mixer"]
    assert float(jnp.abs(
        m["in_proj"] - other["params"]["mamba_1"]["mixer"]["in_proj"]
    ).max()) > 0
    # the time steps: softplus(dt_bias) log-uniform in [1e-3, 1e-1]
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    assert float(jnp.abs(m["conv_bias"]).max()) > 0
    np.testing.assert_array_equal(m["D"], 1.0)
    # A = -(1 .. N) a channel, channels minor
    np.testing.assert_allclose(
        np.exp(m["A_log"][0, :, 7]), np.arange(1, 17), rtol=1e-6)


def test_the_layer_kinds_follow_offset_and_period(tiny):
    cfg, params, _ = tiny
    full = JambaConfig()
    kinds = full.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26 and len(kinds) == 28
    assert full.runs() == (("mamba", 7), ("attention", 1), ("mamba", 13),
                           ("attention", 1), ("mamba", 6))
    assert full.head_dim == 128 and full.d_inner == 5120
    assert cfg.layer_kinds() == ("mamba", "attention", "mamba", "mamba",
                                 "attention", "mamba")
    # a run's parameters are stacked on a leading axis; the head is the
    # token table (no separate matrix)
    assert set(params["params"]) == {
        "embed", "final_norm", "mamba_0", "mamba_1", "mamba_2", "attn_0",
        "attn_1"}
    assert params["params"]["mamba_1"]["mixer"]["in_proj"].shape \
        == (2, 64, 256)
    assert params["params"]["attn_0"]["attn"]["q_proj"].shape == (64, 80)
    assert params["params"]["attn_0"]["attn"]["k_proj"].shape == (64, 16)
    with pytest.raises(ValueError, match="dense members"):
        JambaConfig(num_experts=16)


def test_the_published_sizes_add_up():
    """3,029 M parameters (ISSUE 48's table), counted from the shapes the
    model itself declares."""
    shapes = jax.eval_shape(JambaModel(JambaConfig()).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 3_029_337_472
    mamba = shapes["params"]["mamba_0"]
    per = sum(int(np.prod(a.shape[1:]))
              for a in jax.tree_util.tree_leaves(mamba))
    assert per == 104_161_472       # 41.24 M mixer + 62.91 M SwiGLU + norms


def test_cache_spec_states_pages_for_two_layers_and_state_for_the_rest():
    spec = cache_spec(JambaConfig())
    assert set(spec["pages"]) == {"k_pages", "v_pages"}
    assert spec["pages"]["k_pages"]["layers"] == 2
    assert spec["pages"]["k_pages"]["row"] == 128
    assert spec["pages"]["k_pages"]["q_heads"] == 20
    assert spec["state"]["mamba_state"]["shape"] == (26, 16, 5120)
    assert spec["state"]["mamba_state"]["recurrence"] == "mamba"
    assert spec["state"]["mamba_conv"]["shape"] == (26, 3, 5120)
    # 320 KiB a layer a sequence
    assert np.prod(spec["state"]["mamba_state"]["shape"][1:]) * 4 == 320 << 10
    assert "expert_counts" not in spec and "routed_experts" not in spec


def _cache(cfg, B, slots, bs=8, nb=16, n_slots=5):
    spec = cache_spec(cfg)
    page = spec["pages"]["k_pages"]
    state, conv = (spec["state"][k] for k in ("mamba_state", "mamba_conv"))
    cache = {
        "k_pages": jnp.zeros((page["layers"], 1 + B * nb, bs, page["row"]),
                             cfg.dtype),
        # [layers, slots, ...]: the null slot and four more
        "mamba_state": jnp.zeros(
            (state["shape"][0], n_slots, *state["shape"][1:]), jnp.float32),
        "mamba_conv": jnp.zeros(
            (conv["shape"][0], n_slots, *conv["shape"][1:]), cfg.dtype),
        "block_tables": jnp.asarray(
            1 + np.arange(B * nb).reshape(B, nb), jnp.int32)}
    if slots is not None:
        cache["slots"] = jnp.asarray(slots[:B], jnp.int32)
    cache["v_pages"] = cache["k_pages"]
    return cache


def _served(cfg, params, prompts, n_decode, pad_to, slots=(1, 2, 3)):
    """One padded prefill step of ``prompts`` and ``n_decode`` one-token
    steps through pages and state slots, greedy. Returns each row's
    logits rows and tokens."""
    model = JambaModel(cfg)
    B = len(prompts)
    cache = _cache(cfg, B, slots)
    ids = np.zeros((B, pad_to), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for b, p in enumerate(prompts):
        ids[b, :len(p)] = p
    valid = jnp.arange(pad_to)[None, :] < lens[:, None]
    logits, cache = model.apply(
        params, jnp.asarray(ids), cache=cache,
        seq_lengths=jnp.zeros((B,), jnp.int32), valid=valid,
        logits_at=jnp.asarray(lens - 1))
    rows = [[np.asarray(logits[b, 0])] for b in range(B)]
    tokens = [[int(r[0].argmax())] for r in rows]
    for _ in range(n_decode):
        step = jnp.asarray([[t[-1]] for t in tokens], jnp.int32)
        logits, cache = model.apply(
            params, step, cache=cache, seq_lengths=jnp.asarray(lens),
            valid=jnp.ones((B, 1), bool))
        lens = lens + 1
        for b in range(B):
            rows[b].append(np.asarray(logits[b, 0]))
            tokens[b].append(int(logits[b, 0].argmax()))
    return rows, tokens, cache


def test_prefill_then_decode_equals_the_references_full_forward(tiny):
    """Prompts of unequal length (longer than a scan's chunk, shorter
    than a page) in ONE padded prefill step, then decoding through pages
    and state: every logits row is the reference's full forward's, and
    each row is what it is alone."""
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
    assert float(jnp.abs(cache["mamba_state"][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache["mamba_state"][:, 4]).max()) == 0.0
    assert float(jnp.abs(cache["mamba_state"][:, 2]).max()) > 0.0


def test_the_served_state_is_the_references(tiny):
    """What a slot holds after prefill and decode is the reference's
    state after the same tokens (what the benchmark's probe compares),
    channels minor where the reference's is channels major."""
    cfg, params, sizes = tiny
    prompt = np.random.default_rng(9).integers(0, cfg.vocab_size,
                                               23).tolist()
    _, tokens, cache = _served(cfg, params, [prompt], 4, 32)
    ids = np.asarray(prompt + tokens[0][:-1], np.int32)
    _, want = ref.forward(params["params"], ids, sizes,
                          state_after=len(ids) - 1)
    np.testing.assert_allclose(
        cache["mamba_state"][:, 1], jnp.swapaxes(want, 1, 2), atol=TOL)


def test_padded_rows_and_positions_leave_state_and_tail_bit_for_bit(tiny):
    """A padding row of a prefill bucket (nothing valid, the null slot)
    and of a decode bucket, and a by-slot decode step's free slot: the
    slots they name hold afterwards what they held, state and tail."""
    cfg, params, _ = tiny
    model = JambaModel(cfg)
    rng = np.random.default_rng(3)
    cache = _cache(cfg, 2, (2, 0))
    fill = lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype)  # noqa
    cache["mamba_state"], cache["mamba_conv"] = (
        fill(cache["mamba_state"]), fill(cache["mamba_conv"]))
    before = {k: np.asarray(cache[k]) for k in ("mamba_state", "mamba_conv")}
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    valid = jnp.arange(16)[None, :] < jnp.asarray([9, 0])[:, None]
    _, after = model.apply(params, ids, cache=cache,
                           seq_lengths=jnp.zeros((2,), jnp.int32),
                           valid=valid, logits_at=jnp.asarray([8, 0]))
    for k, was in before.items():
        got = np.asarray(after[k])
        np.testing.assert_array_equal(got[:, [0, 1, 3, 4]],
                                      was[:, [0, 1, 3, 4]])
        assert np.abs(got[:, 2] - was[:, 2]).max() > 1e-3
    # a row whose 9 real positions sit in a bucket of 16 ends where the
    # same 9 end in a bucket of 9
    _, exact = model.apply(params, ids[:1, :9],
                           cache=dict(_cache(cfg, 1, (2,)), **{
                               k: jnp.asarray(v) for k, v in before.items()}),
                           seq_lengths=jnp.zeros((1,), jnp.int32),
                           valid=jnp.ones((1, 9), bool))
    for k in before:
        np.testing.assert_allclose(after[k][:, 2], exact[k][:, 2],
                                   atol=TOL)
    # a decode step in slot order (no ``slots``): row 1 is padding
    by_slot = {k: v for k, v in after.items() if k != "slots"}
    held = {k: np.asarray(by_slot[k]) for k in before}
    _, stepped = model.apply(
        params, ids[:, :1], cache=by_slot,
        seq_lengths=jnp.asarray([9, 0], jnp.int32),
        valid=jnp.asarray([[True], [False]]))
    for k, was in held.items():
        got = np.asarray(stepped[k])
        np.testing.assert_array_equal(got[:, [0, 2, 3, 4]],
                                      was[:, [0, 2, 3, 4]])
        assert np.abs(got[:, 1] - was[:, 1]).max() > 1e-4


def test_the_models_decode_call_site_takes_the_kernel(tiny, monkeypatch):
    """With the chooser patched to the (interpreted) kernel the served
    decode steps give the logits the XLA path gives."""
    cfg, params, _ = tiny
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size,
                                               11).tolist()
    want, _, _ = _served(cfg, params, [prompt], 3, 16)
    monkeypatch.setattr(ssm, "mamba_decode_path",
                        lambda pool, S: "mamba_kernel" if S == 1 else "xla")
    monkeypatch.setattr(ssm, "mamba_step_in_place", _KERNEL)
    got, _, _ = _served(cfg, params, [prompt], 3, 16)
    np.testing.assert_allclose(np.stack(got[0]), np.stack(want[0]),
                               atol=TOL)


@pytest.mark.parametrize("control", ["fp8", "bf16_state"])
def test_a_lower_precision_fails_the_tolerance_float32_passes(tiny, control):
    """The reference's own controls (both operands of every product
    rounded to fp8; the state kept in bfloat16 between tokens) move the
    logits or the state that ``test_the_served_state_is_the_references``
    compares by an order more than ``TOL``: the comparisons above would
    catch either."""
    cfg, params, sizes = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 60)
    want, state = ref.forward(params["params"], ids, sizes, state_after=59)
    quant, state_dtype = {name: (q, s) for name, q, s in ref.CONTROLS}[
        control]
    low, low_state = ref.forward(params["params"], ids, sizes, quant,
                                 state_dtype, state_after=59)
    moved = max(float(jnp.abs(low - want).max()),
                float(jnp.abs(low_state - state).max()))
    assert moved > 10 * TOL, moved
    assert float(jnp.abs(low - want).max()) > 2 * TOL
