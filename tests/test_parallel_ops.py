"""Tests for the parallel layer (mesh/sharding/collectives) and ops
(flash attention kernel in interpret mode, ring attention on the virtual
8-device CPU mesh)."""

import numpy as np
import pytest


def test_mesh_spec_build(cpu_mesh8):
    from ray_tpu.parallel.mesh import MeshSpec
    import jax

    spec = MeshSpec(dp=2, tp=4)
    assert spec.num_devices == 8
    mesh = spec.build(jax.devices("cpu")[:8])
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4


def test_mesh_spec_validation():
    from ray_tpu.parallel.mesh import MeshSpec
    with pytest.raises(ValueError):
        MeshSpec.from_dict({"bogus": 2})
    spec = MeshSpec(tp=4)
    assert spec.with_auto_dp(8).dp == 2


def test_param_sharding_rules(cpu_mesh8):
    import jax.numpy as jnp
    from ray_tpu.parallel.mesh import MeshSpec, shard_params
    import jax

    mesh = MeshSpec(dp=2, tp=4).build(jax.devices("cpu")[:8])
    params = {
        "dense": {"kernel": jnp.ones((256, 512)), "bias": jnp.ones((512,))},
        "out_proj": {"kernel": jnp.ones((512, 256))},
    }
    sharded = shard_params(params, mesh, MeshSpec(dp=2, tp=4))
    # output dim of generic kernels shards over tp
    k_shard = sharded["dense"]["kernel"].sharding.spec
    assert "tp" in str(k_shard)


def test_data_parallel_psum(cpu_mesh8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(dp=8).build(jax.devices("cpu")[:8])
    x = jnp.arange(32.0).reshape(8, 4)
    xs = jax.device_put(x, NamedSharding(mesh, P("dp")))

    @jax.jit
    def mean_all(x):
        return x.mean()

    assert np.isclose(float(mean_all(xs)), float(x.mean()))


def test_collective_group_allreduce(cpu_mesh8):
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel import collectives

    g = collectives.init_collective_group(8, 0, group_name="t",
                                          devices=jax.devices("cpu")[:8])
    x = jnp.ones((8, 4))
    out = g.allreduce(x, op="sum")
    np.testing.assert_allclose(np.asarray(out), np.full((8, 4), 8.0))
    collectives.destroy_collective_group("t")


def test_flash_attention_forward_matches_reference():
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import (attention_reference, flash_attention)

    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (2, 2, 128, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ref = attention_reference(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, force_pallas=True,
                          interpret=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_causal_matches_reference():
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import (attention_reference, flash_attention)

    rng = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (1, 2, 128, 32)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, force_pallas=True,
                          interpret=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_grads_match_reference():
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import (attention_reference, flash_attention)

    rng = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (1, 1, 64, 32)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, force_pallas=True,
                               interpret=True, block_q=32, block_k=32).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_attention_shard_mapped_matches_reference(cpu_mesh8):
    """Under ``attention_mesh`` on a multi-device mesh the kernel runs
    per (batch, head) shard inside shard_map (a Mosaic kernel cannot be
    partitioned by GSPMD): values and grads equal the unsharded
    reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.ops.attention import (attention_mesh, attention_reference,
                                       flash_attention)

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    shape = (4, 8, 64, 32)   # batch over dp=2, heads over tp=4
    sharding = NamedSharding(cpu_mesh8, P("dp", "tp", None, None))
    q, k, v = (jax.device_put(jax.random.normal(r, shape, jnp.float32),
                              sharding) for r in (kq, kk, kv))

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    @jax.jit
    def loss_flash(q, k, v):
        with attention_mesh(cpu_mesh8):
            out = flash_attention(q, k, v, causal=True, force_pallas=True,
                                  interpret=True, block_q=32, block_k=32)
        return (out ** 2).sum()

    np.testing.assert_allclose(float(loss_flash(q, k, v)),
                               float(loss_ref(q, k, v)), rtol=2e-5)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        assert a.sharding.is_equivalent_to(sharding, a.ndim)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_attention_whole_vs_streaming_paths(monkeypatch):
    """The short-sequence whole-kv kernels and the streaming flash
    kernels must agree with each other and the reference — fwd and
    grads (RTPU_ATTN_EXACT=1 forces the streaming path)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    rng = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (2, 2, 256, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    assert A._use_whole_kv(256, 256, 64)

    def loss(fn):
        return lambda q, k, v: fn(q, k, v).sum()

    def flash(q, k, v):
        return A.flash_attention(q, k, v, causal=True, force_pallas=True,
                                 interpret=True, block_q=128, block_k=128)

    ref = A.attention_reference(q, k, v, causal=True)
    out_whole = flash(q, k, v)
    g_whole = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("RTPU_ATTN_EXACT", "1")
    assert not A._use_whole_kv(256, 256, 64)
    out_stream = flash(q, k, v)
    g_stream = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.delenv("RTPU_ATTN_EXACT")

    np.testing.assert_allclose(np.asarray(out_whole), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_stream), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(g_whole, g_stream):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_attention_exact_kwarg_overrides_env(monkeypatch):
    """`exact=` picks the softmax numerics per call (ADVICE round 5:
    the env var was trace-time-only): exact=True forces the streaming
    kernels, exact=False allows the whole-kv fast path, None defers to
    RTPU_ATTN_EXACT — and both paths agree with the reference."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    assert A._use_whole_kv(256, 256, 64)
    assert not A._use_whole_kv(256, 256, 64, True)
    assert A._use_whole_kv(256, 256, 64, False)
    # an explicit exact=False overrides even the env var
    monkeypatch.setenv("RTPU_ATTN_EXACT", "1")
    assert not A._use_whole_kv(256, 256, 64)  # env applies when None
    assert A._use_whole_kv(256, 256, 64, False)
    monkeypatch.delenv("RTPU_ATTN_EXACT")

    rng = jax.random.PRNGKey(11)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (1, 2, 256, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ref = A.attention_reference(q, k, v, causal=True)
    for exact in (True, False):
        out = A.flash_attention(q, k, v, causal=True, force_pallas=True,
                                interpret=True, block_q=128, block_k=128,
                                exact=exact)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        g = jax.grad(lambda q, k, v: A.flash_attention(
            q, k, v, causal=True, force_pallas=True, interpret=True,
            block_q=128, block_k=128, exact=exact).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: A.attention_reference(
            q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)


def test_flash_attention_debug_asserts_on_capped_logits():
    """Debug mode (kwarg or RTPU_ATTN_DEBUG) fails LOUDLY when a logit
    would be silently clamped by the whole-kv path's static cap —
    and stays quiet for in-range logits or the exact streaming path."""
    import jax
    import jax.numpy as jnp
    import pytest
    from ray_tpu.ops import attention as A

    rng = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (1, 1, 128, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    # in-range logits: debug mode is silent
    A.flash_attention(q, k, v, causal=True, force_pallas=True,
                      interpret=True, block_q=64, block_k=64, debug=True)

    # blown-up logits on the capped fast path: loud failure
    with pytest.raises(FloatingPointError, match="_CAP_HI"):
        A.flash_attention(q * 100.0, k, v, causal=True,
                          force_pallas=True, interpret=True,
                          block_q=64, block_k=64, debug=True)

    # the exact streaming path has no cap — same inputs pass
    out = A.flash_attention(q * 100.0, k, v, causal=True,
                            force_pallas=True, interpret=True,
                            block_q=64, block_k=64, debug=True,
                            exact=True)
    assert np.isfinite(np.asarray(out)).all()


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _whole_kv_and_reference(q, k, v, g, causal, sm_scale=None):
    """The whole-kv pair interpreted, and ``attention_reference`` in
    float32 on the same (upcast) inputs: (out, dq, dk, dv) of each."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    def flash(q, k, v):
        return A.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                 force_pallas=True, interpret=True,
                                 exact=False)

    def ref(q, k, v):
        return A.attention_reference(q, k, v, causal=causal,
                                     sm_scale=sm_scale)
    out, vjp = jax.vjp(flash, q, k, v)
    up = [t.astype(jnp.float32) for t in (q, k, v)]
    out_ref, vjp_ref = jax.vjp(ref, *up)
    return ((out,) + vjp(g.astype(out.dtype)),
            (out_ref,) + vjp_ref(g.astype(jnp.float32)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [128, 256, 512, 1024, 2048])
def test_whole_kv_pair_matches_reference(s, d, causal, dtype):
    """Forward and all three gradients of the whole-kv kernels against
    the float32 reference, at every block count the plan gives (1 to 8
    query blocks under ``causal``; the one-block form without)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    assert A._use_whole_kv(s, s, d, False)
    dtype = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(s + d), 4)
    q, k, v, g = (jax.random.normal(r, (1, 2, s, d), jnp.float32
                                    ).astype(dtype) for r in keys)
    got, want = _whole_kv_and_reference(q, k, v, g, causal)
    limit = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == b.shape
        assert _rel(a, b) < limit, (name, _rel(a, b))


def test_whole_kv_causal_blocks_near_the_cap():
    """Logits near ``_CAP_HI`` inside every diagonal block (above the
    diagonal too) and far under it in every other block: a block skipped
    wrongly, or a compare laid on the wrong block, moves the result by
    its whole size."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    s, d = 1024, 64
    plan = A.flash_plan(s, s, d, True, False)
    bq, n = plan["block_q"], s // plan["block_q"]
    assert n == 4
    block = jnp.arange(s) // bq
    own = jax.nn.one_hot(block, d, dtype=jnp.float32)         # [s, d]
    others = (jnp.arange(d)[None, :] < n) - own
    keys = jax.random.split(jax.random.PRNGKey(45), 4)
    noise = [0.05 * jax.random.normal(r, (1, 1, s, d)) for r in keys[:2]]
    q = (46.0 ** 0.5 * own - 20.0 / 46.0 ** 0.5 * others)[None, None] \
        + noise[0]
    k = (46.0 ** 0.5 * own)[None, None] + noise[1]
    v, g = (jax.random.normal(r, (1, 1, s, d)) for r in keys[2:])
    logits = np.asarray(jnp.einsum("bhqd,bhkd->bhqk", q, k))[0, 0]
    same = np.asarray(block[:, None] == block[None, :])
    assert 40.0 < logits[same].min() and logits.max() < A._CAP_HI
    assert logits[~same].max() < -15.0
    got, want = _whole_kv_and_reference(q, k, v, g, True, sm_scale=1.0)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert _rel(a, b) < 1e-4, (name, _rel(a, b))


def test_whole_kv_causal_dk_dv_are_summed_in_float32():
    """At s = 2,048 (8 query blocks) ``dk`` and ``dv`` in bfloat16 lie
    closer to the float32 reference than the same blocks' exact
    contributions summed in a bfloat16 running sum would."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    s, d = 2048, 64
    bq = A.flash_plan(s, s, d, True, False)["block_q"]
    keys = jax.random.split(jax.random.PRNGKey(2048), 4)
    q, k, v, g = (jax.random.normal(r, (1, 1, s, d), jnp.float32
                                    ).astype(jnp.bfloat16) for r in keys)
    got, want = _whole_kv_and_reference(q, k, v, g, True)
    up = [t.astype(jnp.float32) for t in (q, k, v)]
    _, vjp = jax.vjp(lambda q, k, v: A.attention_reference(
        q, k, v, causal=True), *up)
    running = [jnp.zeros((1, 1, s, d), jnp.bfloat16)] * 2
    for lo in range(0, s, bq):
        rows = (jnp.arange(s) >= lo) & (jnp.arange(s) < lo + bq)
        part = vjp(jnp.where(rows[None, None, :, None],
                             g.astype(jnp.float32), 0.0))[1:]
        running = [(r.astype(jnp.float32) + c).astype(jnp.bfloat16)
                   for r, c in zip(running, part)]
    for name, a, b, r in zip(("dk", "dv"), got[2:], want[2:], running):
        assert _rel(a, b) < 0.8 * _rel(r, b), (name, _rel(a, b), _rel(r, b))


@pytest.mark.parametrize("s", [128, 256, 384, 512, 1024, 2048])
def test_flash_plan_counts_the_blocks_it_visits(s):
    from ray_tpu.ops import attention as A

    plan = A.flash_plan(s, s, 64, True, False)
    n = s // plan["block_q"]
    assert plan["path"] == "whole_kv_causal" and s % plan["block_q"] == 0
    assert plan["blocks_visited"] == n * (n + 1) // 2
    assert plan["blocks_total"] == n * n
    full = A.flash_plan(s, s, 64, False, False)
    assert full["path"] == "whole_kv"
    assert full["block_q"] == A._whole_block_q(s)
    assert full["blocks_visited"] == full["blocks_total"] > 0
    exact = A.flash_plan(s, s, 64, True, True, block_q=128, block_k=128)
    m = s // 128
    assert exact == {"path": "streaming", "block_q": 128,
                     "blocks_visited": m * (m + 1) // 2,
                     "blocks_total": m * m}


def test_flash_plan_is_where_the_wrappers_take_their_block(monkeypatch):
    """The kernel's block and the plan's are one number: a plan that
    says 128 at s = 512 makes both kernels walk four query blocks (two
    products a block forward, five backward), and the results stand."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    s, d = 512, 64
    assert A.flash_plan(s, s, d, True, False)["block_q"] == 256
    real, asked = A.flash_plan, []

    def plan128(*args, **kwargs):
        plan = dict(real(*args, **kwargs), block_q=128)
        asked.append(plan)
        return plan
    monkeypatch.setattr(A, "flash_plan", plan128)
    # (the wrappers are jitted: a trace under one plan is not kept for
    # the other, before or after)
    def forget():
        A._whole_forward.clear_cache()
        A._whole_backward.clear_cache()
    forget()
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q, k, v, g = (jax.random.normal(r, (1, 1, s, d)) for r in keys)

    def flash(q, k, v):
        return A.flash_attention(q, k, v, causal=True, force_pallas=True,
                                 interpret=True, exact=False)
    text = str(jax.make_jaxpr(
        lambda q, k, v, g: jax.vjp(flash, q, k, v)[1](g))(q, k, v, g))
    assert text.count("dot_general") == (2 + 5) * (s // 128)
    assert len(asked) == 3              # the event, forward, backward
    got, want = _whole_kv_and_reference(q, k, v, g, True)
    forget()
    for a, b in zip(got, want):
        assert _rel(a, b) < 2e-5


def _heads(t, n):
    """[B, S, n D] -> [B, n, S, D]"""
    b, s, w = t.shape
    return t.reshape(b, s, n, w // n).transpose(0, 2, 1, 3)


def _packed_and_reference(qkv, g, n_head, **kwargs):
    """The packed entry interpreted, and ``attention_reference`` in
    float32 on the same (upcast) ``qkv`` through split + heads + the
    transpose back: (out, dqkv) of each."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    def packed(qkv):
        return A.flash_attention_packed(
            qkv, n_head, **{"causal": True, "force_pallas": True,
                            "interpret": True, "exact": False, **kwargs})

    def ref(qkv):
        q, k, v = (_heads(t, n_head) for t in jnp.split(qkv, 3, axis=-1))
        y = A.attention_reference(q, k, v, causal=True)
        return y.transpose(0, 2, 1, 3).reshape(g.shape)
    out, vjp = jax.vjp(packed, qkv)
    out_ref, vjp_ref = jax.vjp(ref, qkv.astype(jnp.float32))
    return ((out,) + vjp(g.astype(out.dtype)),
            (out_ref,) + vjp_ref(g.astype(jnp.float32)))


def _qkv_and_g(seed, b, s, n_head, d, dtype="float32"):
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(keys[0], (b, s, 3 * n_head * d),
                              jnp.float32).astype(dtype),
            jax.random.normal(keys[1], (b, s, n_head * d),
                              jnp.float32).astype(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s", [256, 1024])
@pytest.mark.parametrize("d,n_head", [(64, 12), (64, 2), (128, 4)])
def test_packed_pair_matches_reference(d, n_head, s, dtype):
    """Forward and the gradient with respect to ``qkv`` of the packed
    kernels (a pair of 64-wide heads, or one 128-wide head, a program;
    blocks cut from [B, S, 3E] where it lies) against the float32
    reference on the same ``qkv``, q's, k's and v's columns apart."""
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    assert A.packed_heads(s, n_head, n_head, d, True, False) == 128 // d
    qkv, g = _qkv_and_g(s + d + n_head, 1, s, n_head, d, dtype)
    got, want = _packed_and_reference(qkv, g, n_head)
    limit = 2e-5 if dtype == "float32" else 2e-2
    assert got[0].shape == g.shape and got[1].shape == qkv.shape
    parts = [("out", got[0], want[0])] + [
        (name, a, b) for name, a, b in zip(
            ("dq", "dk", "dv"), jnp.split(got[1], 3, axis=-1),
            jnp.split(want[1], 3, axis=-1))]
    for name, a, b in parts:
        assert a.dtype == jnp.dtype(dtype)
        assert _rel(a, b) < limit, (name, _rel(a, b))


@pytest.mark.parametrize("d", [64, 32])
def test_packed_heads_of_a_program_do_not_leak(d):
    """The heads of a 128-lane block are told apart by lane: one head's
    keys and values made large (its queries and its share of ``do`` too)
    leave every other head's output and gradients bit for bit."""
    import jax
    import jax.numpy as jnp

    n_head, s = 256 // d, 256
    e = n_head * d
    qkv, g = _qkv_and_g(7, 1, s, n_head, d)
    loud = 1                                    # the block's second head
    cols = (jnp.arange(3 * e) % e) // d == loud
    qkv_loud = jnp.where(cols, 3.0 * qkv + 1.0, qkv)
    g_loud = jnp.where(cols[:e], 5.0 * g, g)
    (out, dqkv), _ = _packed_and_reference(qkv, g, n_head)
    (out_loud, dqkv_loud), _ = _packed_and_reference(qkv_loud, g_loud,
                                                     n_head)
    quiet = ~np.asarray(cols)
    np.testing.assert_array_equal(np.asarray(out)[..., quiet[:e]],
                                  np.asarray(out_loud)[..., quiet[:e]])
    np.testing.assert_array_equal(np.asarray(dqkv)[..., quiet],
                                  np.asarray(dqkv_loud)[..., quiet])
    assert not np.array_equal(np.asarray(out)[..., ~quiet[:e]],
                              np.asarray(out_loud)[..., ~quiet[:e]])


def test_packed_gives_what_the_head_major_kernels_give():
    """One algorithm, two layouts: the same products in the same
    precision, so in bfloat16 the packed pair's output and gradient are
    the [b h, s, d] kernels' (on the chip to the last bit, my chip run,
    PR 51; here a 128-deep float32 sum with 64 zeros in it may round
    another way than the 64-deep one: a few values one bfloat16 step
    apart, 1e-4 of the whole where the float32 reference is 2e-3 away)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    n_head, d, s = 4, 64, 512
    qkv, g = _qkv_and_g(51, 2, s, n_head, d, "bfloat16")

    def head_major(qkv):
        q, k, v = (_heads(t, n_head) for t in jnp.split(qkv, 3, axis=-1))
        y = A.flash_attention(q, k, v, causal=True, force_pallas=True,
                              interpret=True, exact=False)
        return y.transpose(0, 2, 1, 3).reshape(g.shape)
    out, vjp = jax.vjp(head_major, qkv)
    got, _ = _packed_and_reference(qkv, g, n_head)
    assert _rel(got[0], out) < 1e-4, _rel(got[0], out)
    assert _rel(got[1], vjp(g)[0]) < 1e-4, _rel(got[1], vjp(g)[0])


def test_packed_dk_dv_are_summed_in_float32():
    """As ``test_whole_kv_causal_dk_dv_are_summed_in_float32`` holds of
    the [b h, s, d] form: at s = 2,048 (8 query blocks) the packed
    kernel's ``dk`` and ``dv`` in bfloat16 lie closer to the float32
    reference than the blocks' exact parts in a bfloat16 running sum."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A

    s, d, n_head = 2048, 64, 2
    e = n_head * d
    bq = A.flash_plan(s, s, d, True, False)["block_q"]
    qkv, g = _qkv_and_g(2048, 1, s, n_head, d, "bfloat16")
    got, want = _packed_and_reference(qkv, g, n_head)

    def ref(qkv):
        q, k, v = (_heads(t, n_head) for t in jnp.split(qkv, 3, axis=-1))
        y = A.attention_reference(q, k, v, causal=True)
        return y.transpose(0, 2, 1, 3).reshape(g.shape)
    _, vjp = jax.vjp(ref, qkv.astype(jnp.float32))
    running = jnp.zeros((1, s, 2 * e), jnp.bfloat16)
    for lo in range(0, s, bq):
        rows = (jnp.arange(s) >= lo) & (jnp.arange(s) < lo + bq)
        part = vjp(jnp.where(rows[None, :, None],
                             g.astype(jnp.float32), 0.0))[0][..., e:]
        running = (running.astype(jnp.float32) + part).astype(jnp.bfloat16)
    for name, cols in (("dk", slice(e, 2 * e)), ("dv", slice(2 * e, None))):
        a, b = got[1][..., cols], want[1][..., cols]
        r = running[..., cols.start - e:(cols.stop or 3 * e) - e]
        assert _rel(a, b) < 0.8 * _rel(r, b), (name, _rel(a, b), _rel(r, b))


def _tp_mesh():
    import jax
    from ray_tpu.parallel.mesh import MeshSpec
    return MeshSpec(dp=2, tp=2).build(jax.devices("cpu")[:4])


@pytest.mark.parametrize("case,n_head,n_kv,d,kwargs,mesh", [
    ("an odd head count", 3, 3, 64, {}, None),
    ("a head of 96", 2, 2, 96, {}, None),
    ("no causal", 2, 2, 64, {"causal": False}, None),
    ("exact", 2, 2, 64, {"exact": True}, None),
    ("grouped kv heads", 4, 2, 64, {}, None),
    ("a tp mesh", 4, 4, 64, {}, _tp_mesh)], ids=lambda v: v if isinstance(
        v, str) else "")
def test_what_the_packed_kernels_cannot_serve_takes_the_heads(
        cpu_mesh8, case, n_head, n_kv, d, kwargs, mesh):
    """The shape rule: each of these is split + heads +
    ``flash_attention`` + the transpose back, traced so (the event says
    ``packed`` false, a transpose is in the jaxpr) and with that answer,
    forward and gradient."""
    import contextlib
    from collections import deque
    import jax
    import jax.numpy as jnp
    from ray_tpu._private import tracing
    from ray_tpu.ops import attention as A

    s = 256
    mesh = mesh and mesh()
    kwargs = {"causal": True, "exact": False, "force_pallas": True,
              "interpret": True, **kwargs}
    assert A.packed_heads(s, n_head, n_kv, d, kwargs["causal"],
                          kwargs["exact"], mesh) == 0
    keys = jax.random.split(jax.random.PRNGKey(n_head + d), 2)
    qkv = jax.random.normal(keys[0], (2, s, (n_head + 2 * n_kv) * d))
    g = jax.random.normal(keys[1], (2, s, n_head * d))

    def packed(qkv):
        return A.flash_attention_packed(qkv, n_head, n_kv_head=n_kv,
                                        **kwargs)

    def old(qkv):
        q, k, v = jnp.split(qkv, [n_head * d, (n_head + n_kv) * d], axis=-1)
        k, v = (jnp.repeat(_heads(t, n_kv), n_head // n_kv, axis=1)
                for t in (k, v))
        y = A.flash_attention(_heads(q, n_head), k, v, **kwargs)
        return y.transpose(0, 2, 1, 3).reshape(g.shape)
    ring = deque()
    with A.attention_mesh(mesh) if mesh else contextlib.nullcontext():
        with tracing.step_span("test.rule", ring):
            text = str(jax.make_jaxpr(packed)(qkv))
        got, vjp = jax.vjp(packed, qkv)
        want, vjp_old = jax.vjp(old, qkv)
        dgot, dwant = vjp(g)[0], vjp_old(g)[0]
    plans = [e["attrs"] for e in ring[0]["children"]
             if e["name"] == "attention.flash_plan"]
    assert [(p["packed"], p["heads_per_program"]) for p in plans] \
        == [(False, 1)]
    assert "transpose" in text
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(dgot), np.asarray(dwant))


def test_packed_path_traces_no_split_and_no_transpose():
    """Where the packed kernels run nothing is laid out anew for them:
    forward + backward are the two kernels (the backward's [1, bq] rows
    of ``lse`` are turned inside it) and the event says so."""
    from collections import deque
    import jax
    from ray_tpu._private import tracing
    from ray_tpu.ops import attention as A

    qkv, g = _qkv_and_g(3, 2, 256, 4, 64)
    ring = deque()

    def packed(qkv):
        return A.flash_attention_packed(qkv, 4, causal=True,
                                        force_pallas=True, interpret=True)
    with tracing.step_span("test.packed", ring):
        jaxpr = jax.make_jaxpr(
            lambda qkv, g: jax.vjp(packed, qkv)[1](g))(qkv, g)
    outside = {str(eqn.primitive) for eqn in jaxpr.jaxpr.eqns}
    assert not outside & {"transpose", "split", "concatenate", "slice",
                          "reshape", "mul"}, outside
    plans = [e["attrs"] for e in ring[0]["children"]
             if e["name"] == "attention.flash_plan"]
    assert plans == [{**A.flash_plan(256, 256, 64, True),
                      "packed": True, "heads_per_program": 2}]


def test_packed_batch_mesh_runs_each_device_its_rows(cpu_mesh8):
    """A mesh that shards the batch alone keeps the packed kernels, each
    device its rows inside ``shard_map``; the answer is the one
    device's."""
    import jax
    from ray_tpu.ops import attention as A
    from ray_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(dp=2, fsdp=2).build(jax.devices("cpu")[:4])
    assert A.packed_heads(256, 2, 2, 64, True, False, mesh) == 2
    qkv, g = _qkv_and_g(4, 4, 256, 2, 64)
    alone, _ = _packed_and_reference(qkv, g, 2)
    with A.attention_mesh(mesh):
        text = str(jax.make_jaxpr(lambda x: A.flash_attention_packed(
            x, 2, causal=True, force_pallas=True, interpret=True))(qkv))
        meshed, _ = _packed_and_reference(qkv, g, 2)
    assert "shard_map" in text
    for a, b in zip(meshed, alone):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_gpt2_with_the_packed_kernels_matches_the_reference_backend(
        monkeypatch):
    """The model's loss and parameter gradients through the packed path
    (two 64-wide heads: one pair a program) against
    ``attention_backend="reference"`` with the same parameters."""
    import dataclasses
    import functools
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.gpt2 import GPT2, GPT2Config
    from ray_tpu.ops import attention as A

    flash = dataclasses.replace(
        GPT2Config.tiny(), n_embd=128, n_head=2, attention_backend="flash")
    ref = dataclasses.replace(flash, attention_backend="reference")
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                             flash.vocab_size)
    params = GPT2(ref).init(jax.random.PRNGKey(0), ids)
    calls = []

    def interpreted(qkv, n_head, **kwargs):
        calls.append(A.packed_heads(qkv.shape[1], n_head, n_head,
                                    qkv.shape[2] // 3 // n_head, True))
        return real(qkv, n_head, force_pallas=True, interpret=True,
                    **kwargs)
    real = A.flash_attention_packed
    monkeypatch.setattr(A, "flash_attention_packed", interpreted)

    def loss(cfg):
        def f(params):
            logits = GPT2(cfg).apply(params, ids)
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            return -jnp.take_along_axis(
                logp, ids[:, 1:, None], axis=-1).mean()
        return jax.value_and_grad(f)(params)
    got, dgot = loss(flash)
    want, dwant = loss(ref)
    assert calls == [2] * flash.n_layer
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for a, b in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        assert _rel(a, b) < 1e-4, _rel(a, b)


def test_ring_attention_matches_full(cpu_mesh8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ray_tpu.ops.attention import attention_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded

    devices = jax.devices("cpu")[:4]
    mesh = Mesh(np.array(devices), ("sp",))
    rng = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (1, 2, 64, 16)  # seq 64 over 4 devices = 16 local
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    ref = attention_reference(q, k, v, causal=False)
    out = ring_attention_sharded(q, k, v, mesh, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_causal_matches_full(cpu_mesh8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ray_tpu.ops.attention import attention_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded

    devices = jax.devices("cpu")[:4]
    mesh = Mesh(np.array(devices), ("sp",))
    rng = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (2, 2, 64, 16)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    ref = attention_reference(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
