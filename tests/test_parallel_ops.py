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
