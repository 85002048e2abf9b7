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
