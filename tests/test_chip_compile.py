"""The main path's kernels compile for the chip, at real widths.

The TPU's compiler is installed and compiles for a v5e that is described,
not attached (on-chip-measurement guide, section 2). Interpret mode never
sees what it refuses: tiling, VMEM, Mosaic's partitioning rule. Nothing
runs here, so these tests say nothing about results or times.

This is the only file that describes the topology, and it does so inside
a module-scoped fixture: only one process may load libtpu, so the call
must not happen at import, in a ``skipif``, in ``parametrize`` arguments
or in ``conftest.py``, and no test here may compile in a child process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from ray_tpu.ops import attention as A
from ray_tpu.parallel.mesh import MeshSpec


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep it off for this module
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    # conftest.py pins f32-exact matmuls for the CPU numerics tests; the
    # chip path compiles at the default precision, as production does
    with jax.default_matmul_precision("default"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _qkv(shape, sharding):
    return (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding),) * 3


def _flash(exact, grad):
    def fwd(q, k, v):
        return A.flash_attention(q, k, v, causal=True, force_pallas=True,
                                 exact=exact)
    if not grad:
        return fwd
    return jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


def _flash_packed(exact, grad, n_head, mesh=None):
    def fwd(qkv):
        with A.attention_mesh(mesh):
            return A.flash_attention_packed(qkv, n_head, causal=True,
                                            force_pallas=True, exact=exact)
    if not grad:
        return fwd
    return jax.grad(lambda qkv: fwd(qkv).astype(jnp.float32).sum())


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("exact", [False, True],
                         ids=["whole-kv", "streaming"])
@pytest.mark.parametrize("packed", [False, True],
                         ids=["heads", "packed-entry"])
def test_flash_attention_gpt2_small_shape(one_chip, packed, exact, grad):
    """[16, 12, 1024, 64] bf16 causal: the GPT-2 small train step's
    attention, on both sides of ``_use_whole_kv``; through the packed
    entry it is ``c_attn``'s [16, 1024, 2304] where it lies (``exact``:
    the entry lays the heads out and takes the streaming kernels)."""
    assert A._use_whole_kv(1024, 1024, 64, exact) is (not exact)
    if not packed:
        _compile(_flash(exact, grad), *_qkv((16, 12, 1024, 64), one_chip))
        return
    assert A.packed_heads(1024, 12, 12, 64, True, exact) == (
        0 if exact else 2)
    text = _compile(_flash_packed(exact, grad, 12),
                    *_qkv((16, 1024, 2304), one_chip)[:1])
    assert bool(_materialized(text, "bf16[16,12,1024,64]",
                              ops=("copy", "transpose"))) is exact


@pytest.mark.parametrize("b,s,n_head,d", [
    (2, 2048, 8, 64), (2, 2048, 8, 128), (2, 256, 4, 32)],
    ids=["pairs-s2048", "one-head-s2048", "four-heads-s256"])
def test_flash_attention_packed_other_shapes(one_chip, b, s, n_head, d):
    """The packed pair at the longest rows the whole-kv path admits (a
    pair of 64-wide heads: eight query blocks a head unrolled twice in
    one program; a 128-wide head alone) and with four 32-wide heads a
    block: forward + backward inside the chip's VMEM."""
    assert A.packed_heads(s, n_head, n_head, d, True, False) == 128 // d
    text = _compile(_flash_packed(False, True, n_head),
                    *_qkv((b, s, 3 * n_head * d), one_chip)[:1])
    assert text.count("tpu_custom_call") == 2


def test_flash_attention_packed_on_a_batch_mesh(topo):
    """A mesh that shards the batch alone keeps the packed kernels, each
    chip its rows inside ``shard_map``: forward + backward compile for
    four chips, and nothing is gathered or laid out anew round them."""
    mesh = MeshSpec(dp=2, fsdp=2).build(list(topo.devices))
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), None, None))
    assert A.packed_heads(1024, 12, 12, 64, True, False, mesh) == 2
    text = _compile(_flash_packed(False, True, 12, mesh),
                    *_qkv((32, 1024, 2304), sharding)[:1])
    assert text.count("tpu_custom_call") == 2
    assert "all-gather" not in text and "bf16[8,1024,2304]" in text
    assert not _materialized(text, "bf16[8,12,1024,64]", "bf16[8,1024,",
                             ops=("copy", "transpose"))


def test_train_step_lays_nothing_out_anew_for_its_attention(topo,
                                                            monkeypatch):
    """The train step of the cell gpt2_small.train_fed at its real shape
    (b16 x s1024, 12 heads of 64, bfloat16) lowers with the packed
    kernels, one forward and one backward a layer, and its optimised
    program holds no copy or transpose whose result is [16, 12, 1024, 64]
    or [16, 1024, 768] (before PR 51: 144 of them, 9.5 ms of a 113 ms
    step on the chip, ledger, PR 50), nor one of ``c_attn``'s
    [16, 1024, 2304] or its gradient; and it keeps less beside its
    arguments than it did (8,097,406,976 bytes of temporaries then)."""
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.train.spmd import make_causal_lm_trainer

    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                     n_layer=12, n_head=12, dtype=jnp.bfloat16,
                     attention_backend="flash")
    spec = MeshSpec()
    trainer = make_causal_lm_trainer(
        cfg, mesh=spec.build(list(topo.devices)[:1]), spec=spec)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(trainer.init, jax.random.PRNGKey(0)),
        trainer.state_sharding_tree)
    batch = {k: jax.ShapeDtypeStruct((16, 1024), jnp.int32, sharding=s)
             for k, s in trainer.batch_shardings.items()}
    with jax.default_matmul_precision("default"):
        step = trainer.step.lower(state, batch).compile()
    text = step.as_text()
    assert text.count("tpu_custom_call") == 2 * cfg.n_layer
    assert text.count("bf16[16,1024,2304]") > 0
    assert not _materialized(text, "bf16[16,12,1024,64]",
                             "bf16[16,1024,768]", "bf16[16,1024,2304]",
                             ops=("copy", "transpose"))
    assert step.memory_analysis().temp_size_in_bytes < 7 << 30


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_largest_whole_kv_shape(one_chip, grad):
    """[2, 8, 2048, 128] bf16 causal, the longest and widest the
    whole-kv path admits: eight query blocks unrolled in one program a
    head, inside the chip's VMEM."""
    assert A._use_whole_kv(2048, 2048, 128, False)
    assert not A._use_whole_kv(2048 + 128, 2048 + 128, 128, False)
    assert not A._use_whole_kv(2048, 2048, 256, False)
    assert A.flash_plan(2048, 2048, 128, True, False) == {
        "path": "whole_kv_causal", "block_q": 256,
        "blocks_visited": 36, "blocks_total": 64}
    _compile(_flash(False, grad), *_qkv((2, 8, 2048, 128), one_chip))


def test_flash_attention_backward_keeps_no_score_square(one_chip):
    """The GPT-2 small shape's forward + backward keeps no more outside
    its kernels than the one-block form did (432.4 MiB of temporaries at
    PR 44; 384.2 now that the kernel takes ``delta``, 128 lanes a value
    in HBM, itself): no [1024, 1024] float32 square of scores, 768 MiB
    over the heads, reaches HBM."""
    with jax.default_matmul_precision("default"):
        step = jax.jit(_flash(False, True)).lower(
            *_qkv((16, 12, 1024, 64), one_chip)).compile()
    text = step.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "1024,1024]" not in text
    assert step.memory_analysis().temp_size_in_bytes <= 453_371_904


def test_flash_attention_streaming_long_wide(one_chip):
    """[1, 32, 4096, 128]: past the whole-kv limit, head dim 128."""
    assert not A._use_whole_kv(4096, 4096, 128, None)
    _compile(_flash(None, False), *_qkv((1, 32, 4096, 128), one_chip))


def _mesh4(topo):
    spec = MeshSpec(dp=2, tp=2)
    mesh = spec.build(list(topo.devices))
    return mesh, NamedSharding(mesh, P(("dp", "fsdp"), "tp", None, None))


def test_flash_attention_shard_mapped_on_dp2_tp2(topo):
    """Under ``attention_mesh`` the kernel runs per (batch, head) shard
    inside ``shard_map``: forward and backward compile for four chips."""
    mesh, sharding = _mesh4(topo)

    def loss(q, k, v):
        with A.attention_mesh(mesh):
            return A.flash_attention(q, k, v, causal=True,
                                     force_pallas=True).astype(
                                         jnp.float32).sum()
    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             *_qkv((32, 12, 1024, 64), sharding))


def test_bare_pallas_call_is_refused_on_a_mesh(topo):
    """The rule the wrap exists for: GSPMD cannot partition a Mosaic
    kernel, so without the mesh context the same program is refused."""
    _, sharding = _mesh4(topo)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(_flash(None, False), *_qkv((32, 12, 1024, 64), sharding))


@pytest.mark.parametrize("H,Hkv,D", [(12, 12, 64), (32, 8, 128),
                                     (32, 8, 64)],
                         ids=["gpt2-small", "llama-gqa", "lfm2-gqa-64"])
def test_paged_attention_decode(one_chip, H, Hkv, D):
    """B=8 sequences against a pool of 4 layers of 16-token pages, 64
    pages each, the layer a traced scalar."""
    B, bs, NB = 8, 16, 64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = sds((4, 1 + B * NB, bs, Hkv * D), jnp.bfloat16)
    _compile(lambda q, k, v, bt, ln, layer: A.paged_attention_decode(
        q, k, v, bt, ln, layer=layer, interpret=False),
             sds((B, H, D), jnp.bfloat16), pool, pool,
             sds((B, NB), jnp.int32), sds((B,), jnp.int32),
             sds((), jnp.int32))


@pytest.mark.parametrize("L,P,B,H,NB", [(7, 18433, 32, 64, 576),
                                        (2, 12289, 64, 32, 192),
                                        (8, 12289, 64, 64, 192)],
                         ids=["kimi-k2-cell", "kimi-linear-cell",
                              "longcat-flash-cell"])
def test_latent_attention_decode(one_chip, L, P, B, H, NB):
    """The latent-decode kernel at the three latent cells' real shapes:
    the pool [L, P, 16, 640] bfloat16, the whole block table as scalar
    prefetch ([32, 576] int32 is 74 KB of scalar memory), the layer a
    traced scalar. The groups of a chunk and the pages of a group that is
    no run are loops inside the kernel, as the paged kernel's: it starts
    a copy at 4 sites (a run's and a page's, at the first chunk and at
    the next one), under 1 + ``PAGED_RUN_PAGES``, and waits at 1, where
    the parent's had 128 (2 x the 64 pages of its chunk) and 1; counted
    in the kernel's jaxpr, each equation of which Mosaic lowers once."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, pool, bt, ln, layer):
        return A.latent_attention_decode(
            q, pool, bt, ln, rank=512, sm_scale=0.1, layer=layer)
    args = (sds((B, H, 640), jnp.bfloat16),
            sds((L, P, 16, 640), jnp.bfloat16), sds((B, NB), jnp.int32),
            sds((B,), jnp.int32), sds((), jnp.int32))
    _compile(call, *args)
    text = str(jax.make_jaxpr(call)(*args))
    assert "latent_attention_decode" in text
    assert text.count("dma_start") == 4 <= 1 + A.PAGED_RUN_PAGES
    assert text.count("dma_wait") == 1


def _kda_step(sharding, B=64, slots=65, layers=6, H=32, d=128):
    """The KDA decode kernel at the Kimi-Linear cell's shape: 64 rows
    over the state pool [6, 65, 32, 128, 128] float32 where it lies, the
    layer a traced scalar, each row's slot an array."""
    from ray_tpu.ops import linear_attention as LA

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    vec = sds((B, H, d))
    return (lambda q, k, v, g, beta, pool, layer, at:
            LA.kda_recurrent_step_in_place(q, k, v, g, beta, pool, layer,
                                           at)), (
        vec, vec, vec, vec, sds((B, H)), sds((layers, slots, H, d, d)),
        sds((), jnp.int32), sds((B,), jnp.int32))


def test_kda_recurrence_decode(one_chip):
    """One Mosaic call; the pool is its own output (donated: no bytes
    beside the arguments), and nothing of a layer's rows of state is
    copied, sliced or written back outside it."""
    import math
    fn, args = _kda_step(one_chip)
    with jax.default_matmul_precision("default"):
        big = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile()
    text = big.as_text()
    assert text.count("tpu_custom_call") == 1 and "kda_recurrence" in text
    memory = big.memory_analysis()
    pool = math.prod(args[5].shape) * 4
    assert memory.alias_size_in_bytes == pool
    assert memory.temp_size_in_bytes < (1 << 20)
    assert not _moved(text, 64 * 32 * 128 * 128)


def test_bare_kda_kernel_is_refused_on_a_mesh(topo, monkeypatch):
    """As the other kernels': a Mosaic call cannot be partitioned, so
    ``kda_decode_path`` says ``xla`` where a mesh of several devices is
    being traced for, and the bare call is refused there."""
    from ray_tpu.ops import linear_attention as LA
    mesh, _ = _mesh4(topo)
    fn, args = _kda_step(NamedSharding(mesh, P()), B=8, slots=9, layers=1)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(fn, *args)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert LA.kda_decode_path(args[5], 1) == "kda_kernel"
    with A.attention_mesh(mesh):
        assert LA.kda_decode_path(args[5], 1) == "xla"


def _routed_experts(monkeypatch, T, sharding, mesh=None):
    """The few-token product at Kimi-Linear's widths (64 held experts of
    3 x 2304 x 1024 bfloat16) as the chip runs it: the Mosaic kernel,
    not its interpretation."""
    from ray_tpu.ops.routed_experts import touched_experts
    E, d, d_ff = 64, 2304, 1024

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def product(*args):
        with A.attention_mesh(mesh):
            return touched_experts(*args)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    return _compile(
        product, sds((T, d), jnp.bfloat16), sds((T, E), jnp.float32),
        sds((E,), jnp.int32), sds((E, d, d_ff), jnp.bfloat16),
        sds((E, d, d_ff), jnp.bfloat16), sds((E, d_ff, d), jnp.bfloat16))


@pytest.mark.parametrize("T", [1, 64, 256])
def test_routed_experts_few_tokens(one_chip, monkeypatch, T):
    """One row (padded to a sublane tile), a full decode batch, and the
    most rows that go this way; no product over the whole stack beside
    the kernel."""
    text = _routed_experts(monkeypatch, T, one_chip)
    assert " convolution(" not in text and " dot(" not in text


def test_routed_experts_shard_mapped_on_dp2_tp2(topo, monkeypatch):
    """Under ``attention_mesh`` the kernel runs inside ``shard_map`` on
    every device's own copy; without it the mesh refuses it."""
    mesh, _ = _mesh4(topo)
    _routed_experts(monkeypatch, 64, NamedSharding(mesh, P()), mesh)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _routed_experts(monkeypatch, 64, NamedSharding(mesh, P()))


def _grouped_experts(monkeypatch, sharding, T, top_k, experts, held, d,
                     d_ff, mesh=None):
    """The many-token product's kernel at a cell's widths, over a chunk
    of the rows ``moe.expert_product`` gives for its prompt."""
    from ray_tpu.ops.routed_experts import grouped_experts
    from ray_tpu.parallel.moe import expert_product
    plan = expert_product(T, top_k, experts, held, d)
    assert plan.name == "grouped_kernel"
    R, bm = plan.chunk_rows, plan.block_rows

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def product(*args):
        with A.attention_mesh(mesh):
            return grouped_experts(*args, bm)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    return _compile(
        product, sds((R, d), jnp.bfloat16), sds((R,), jnp.float32),
        sds((R // bm,), jnp.int32), sds((1,), jnp.int32),
        sds((held, d, d_ff), jnp.bfloat16),
        sds((held, d, d_ff), jnp.bfloat16),
        sds((held, d_ff, d), jnp.bfloat16))


@pytest.mark.parametrize("T,top_k,experts,held,d,d_ff", [
    (8192, 8, 256, 256, 2048, 512), (8192, 8, 384, 12, 7168, 2048),
    (2048, 8, 256, 64, 2304, 1024), (512, 8, 256, 64, 2304, 1024)],
    ids=["laguna", "kimi_k2", "kimi_linear_2048", "kimi_linear_512"])
def test_routed_experts_many_tokens(one_chip, monkeypatch, T, top_k,
                                    experts, held, d, d_ff):
    """The sorted row blocks ``moe.expert_product`` holds at a time
    through their experts, at the three cells' widths (a Laguna prompt's
    131,072 rows whole through 6.3 MB experts; three blocks of a Kimi-K2
    prompt's through an 88 MB expert at a 256-wide tile; Kimi-Linear's
    whole under 128-row blocks through 14 MB experts): one Mosaic kernel
    and no product beside it."""
    text = _grouped_experts(monkeypatch, one_chip, T, top_k, experts, held,
                            d, d_ff)
    assert " convolution(" not in text and " dot(" not in text


def test_grouped_experts_shard_mapped_on_dp2_tp2(topo, monkeypatch):
    mesh, _ = _mesh4(topo)
    _grouped_experts(monkeypatch, NamedSharding(mesh, P()), 2048, 8, 256,
                     64, 2304, 1024, mesh)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _grouped_experts(monkeypatch, NamedSharding(mesh, P()), 2048, 8,
                         256, 64, 2304, 1024)


def _moved(text, count):
    """The program's copies, pads, update-slices and concatenates of at
    least ``count`` elements: what a pool written in place has none of."""
    import math
    import re
    return [
        line.strip()[:120] for line in text.splitlines()
        for m in [re.search(r" = \w+\[([\d,]+)\]\S* (copy|copy-start|pad|"
                            r"dynamic-update-slice|concatenate)\(", line)]
        if m and math.prod(map(int, m.group(1).split(","))) >= count]


def _served_step(one_chip, topo, monkeypatch, pages, B, S, full,
                 n_layer=4):
    """``FlaxModelAdapter``'s jitted step at GPT-2 large's width, cut to
    4 layers, compiled for the chip over a pool of ``pages`` pages, its
    attention as the chip chooses it (the paged kernel for ``S == 1``)."""
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    adapter = FlaxModelAdapter(
        "gpt2", GPT2Config(n_embd=1280, n_layer=n_layer, n_head=20),
        params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    # the adapter's own pool shape, its page axis at the size asked for
    adapter.bind_cache(PagedKVCache(2, 16))
    layers, _, *page = adapter.k_pages.shape
    pool = sds((layers, pages, *page), adapter.k_pages.dtype)
    with monkeypatch.context() as m:
        # the adapter donates the pools when its first device is a TPU
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S, full)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return pool, fn.lower(
            params, sds((B, S), jnp.int32), pool, pool,
            sds((B, adapter.nb_max), jnp.int32), sds((B,), jnp.int32),
            sds((B, S), jnp.bool_)).compile()


@pytest.mark.parametrize("B,S,full", [
    (16, 1, False), (1, 256, False), (2, 8, True)],
    ids=["decode", "prefill", "verify"])
def test_served_step_writes_the_pool_in_place(one_chip, topo, monkeypatch,
                                              B, S, full):
    """The donated pools are the program's outputs, nothing of a pool's
    size is copied, laid out anew, padded or stacked, and what the
    program needs beside its arguments does not grow with the pool."""
    import math
    pool, big = _served_step(one_chip, topo, monkeypatch, 1025, B, S, full)
    count = math.prod(pool.shape)
    memory = big.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * count * pool.dtype.itemsize
    assert not _moved(big.as_text(), count)
    # (a pool of some tens of MB the compiler keeps in its fast memory,
    # which no served pool fits: hence no fewer pages than these)
    _, twice = _served_step(one_chip, topo, monkeypatch, 2049, B, S, full)
    assert memory.temp_size_in_bytes \
        == twice.memory_analysis().temp_size_in_bytes


def test_decode_step_attends_to_its_live_pages_in_place(one_chip, topo,
                                                        monkeypatch):
    """GPT-2 large's decode program as served (16 rows, 36 layers, 1,025
    pages of 16 x 1,280 bfloat16): the paged kernel is in it, no operand
    or temporary spans the 16 rows' 1,024 padded positions (the gather,
    its re-layout into heads, the scores), nothing of a pool's size is
    copied, and the prefill program beside it still gathers."""
    import math
    import re
    pool, step = _served_step(one_chip, topo, monkeypatch, 1025, 16, 1,
                              False, n_layer=36)
    text = step.as_text()
    assert "tpu_custom_call" in text
    count = math.prod(pool.shape)
    shapes = [tuple(map(int, m.group(1).split(",")))
              for m in re.finditer(r"\w+\[([\d,]+)\]", text)]
    padded = {s for s in shapes if 1024 in s and 16 in s}
    assert not padded, padded
    assert not _moved(text, count)
    assert step.memory_analysis().alias_size_in_bytes \
        == 2 * count * pool.dtype.itemsize
    _, prefill = _served_step(one_chip, topo, monkeypatch, 1025, 16, 8,
                              False)
    # (the blocks' products are Mosaic calls in every program since PR 50:
    # it is the attention's call that a prefill must not hold)
    assert "paged_attention_decode" in text
    assert "paged_attention_decode" not in prefill.as_text()


def _materialized(text, *shapes, ops=None):
    """The instructions outside fused computations (the entry, a loop's
    body: what is written to HBM) whose result is one of ``shapes``
    (prefixes, as ``"bf16[36,1280,"``); with ``ops`` only those of these
    opcodes or fusions the compiler named for one (``("copy",
    "transpose")``: an array written again only to lie another way; a
    ``copy-start`` / ``copy-done`` pair moves it between memories as it
    lies and is not one)."""
    import re
    out, fused = [], False
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(", line)
        if head:
            fused = head.group(2).startswith("fused_computation")
        m = re.search(r"%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(", line)
        if m and not fused and m.group(2).startswith(shapes) and (
                ops is None or m.group(3) in ops
                or m.group(3) == "fusion" and m.group(1).startswith(ops)):
            out.append(line.strip()[:140])
    return out


@pytest.mark.parametrize("B,S", [(16, 1), (1, 512)],
                         ids=["decode", "prefill"])
def test_served_programs_cast_no_weight_stack_through_hbm(one_chip, topo,
                                                          monkeypatch, B, S):
    """GPT-2 large's programs as served (36 layers): the blocks' four
    products are ``stacked_linear``'s kernel over the float32 stacks where
    they lie, so no program writes a bfloat16 copy of a stack or of the
    token table, and what a program needs beside its arguments is not
    1.44 GiB (1,545,823,744 bytes for the decode program before PR 50:
    the compiler cast all 36 layers ahead of the loop) but under 256 MiB.
    The tied head's cast of the table is fused into its product."""
    pool, step = _served_step(one_chip, topo, monkeypatch, 1025, B, S,
                              False, n_layer=36)
    text = step.as_text()
    assert "stacked_linear" in text
    assert not _materialized(text, "bf16[36,1280,", "bf16[36,5120,",
                             "bf16[50257,1280]")
    memory = step.memory_analysis()
    assert memory.temp_size_in_bytes < (256 << 20)
    assert memory.alias_size_in_bytes \
        == 2 * pool.dtype.itemsize * pool.size


def _stacked_linear(sharding, M, K, N, L=36):
    from ray_tpu.ops.linear import stacked_linear_kernel

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return stacked_linear_kernel, (
        sds((M, K), jnp.bfloat16), sds((L, K, N), jnp.float32),
        sds((L, N), jnp.float32), sds((), jnp.int32))


@pytest.mark.parametrize("M", [16, 512])
@pytest.mark.parametrize("K,N", [(1280, 3840), (1280, 1280), (1280, 5120),
                                 (5120, 1280)],
                         ids=["c_attn", "attn.c_proj", "c_fc", "mlp.c_proj"])
def test_stacked_linear(one_chip, M, K, N):
    """GPT-2 large's four products for a decode batch and a prompt: one
    Mosaic call over the float32 stack, nothing of a layer's matrix
    sliced, cast or copied outside it."""
    fn, args = _stacked_linear(one_chip, M, K, N)
    with jax.default_matmul_precision("default"):
        big = jax.jit(fn).lower(*args).compile()
    text = big.as_text()
    assert text.count("tpu_custom_call") == 1 and "stacked_linear" in text
    assert big.memory_analysis().temp_size_in_bytes < (1 << 20)
    assert not _moved(text, K * N)


def test_bare_stacked_linear_is_refused_on_a_mesh(topo, monkeypatch):
    """As the other kernels': a Mosaic call cannot be partitioned, so
    ``stacked_linear_path`` says ``xla`` where a mesh of several devices
    is being traced for, and the bare call is refused there."""
    from ray_tpu.ops import linear as LN
    mesh, _ = _mesh4(topo)
    fn, args = _stacked_linear(NamedSharding(mesh, P()), 16, 1280, 1280, L=2)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(fn, *args)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert LN.stacked_linear_path(*args[:2]) == "kernel"
    with A.attention_mesh(mesh):
        assert LN.stacked_linear_path(*args[:2]) == "xla"


def _last_tokens(sds, pages, S):
    """What a decode program (``S == 1``) of a model that states its
    cache takes before its pools, donated like them: the last decode
    step's greedy tokens by row (``FlaxModelAdapter._last_tokens``)."""
    from ray_tpu.serve.llm.model_runner import _pad_pow2
    return [sds((_pad_pow2(pages),), jnp.int32)] if S == 1 else []


def _kimi_step(one_chip, topo, monkeypatch, pages, slots, B, S):
    """``FlaxModelAdapter``'s step for Kimi-Linear at the published
    widths, cut to one period (K K K M), 8 experts and 2048 rows of the
    vocabulary, over ``pages`` latent pages and ``slots`` state slots."""
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    cfg = KimiLinearConfig(vocab_size=2048, num_hidden_layers=4,
                           experts_held=(0, 8), max_seq_len=1024)
    adapter = FlaxModelAdapter("kimi_linear", cfg, params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    adapter.bind_cache(PagedKVCache(2, 16))
    adapter.bind_state(1)
    adapter.state_slots = slots      # B == slots: rows in slot order
    arrays = []
    for name, a in adapter._arrays.items():
        n = pages if name in adapter._spec["pages"] else slots + 1
        arrays.append(sds((a.shape[0], n, *a.shape[2:]), a.dtype))
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S)
    # the routed experts' kernel as the chip runs it, not interpreted
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return arrays, fn.lower(
            params, sds((B, S + 3 + adapter.nb_max), jnp.int32),
            *_last_tokens(sds, pages, S), *arrays).compile()


@pytest.mark.parametrize("B,S,slots", [(16, 1, 32), (16, 1, 16),
                                       (1, 256, 32)],
                         ids=["decode", "decode_by_slot", "prefill"])
def test_kimi_step_writes_pages_and_state_in_place(one_chip, topo,
                                                   monkeypatch, B, S, slots):
    """The latent pool and both state arrays are donated and come back
    as the program's outputs; nothing of the pool's or the state's size
    is copied, laid out anew, padded or stacked; what the program needs
    beside its arguments grows with neither. A decode step's recurrence
    is one Mosaic call a KDA layer over the state pool where it lies,
    rows by ``slots`` and rows in slot order alike: nothing of a layer's
    rows of state (B x H x dk x dv) or more is sliced, gathered, copied
    or written back outside it."""
    import math
    import re
    arrays, big = _kimi_step(one_chip, topo, monkeypatch, 2049, slots, B, S)
    memory = big.memory_analysis()
    if S == 1:
        text = big.as_text()
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and "kda/recurrence" in line]
        assert len(calls) == arrays[1].shape[0], calls      # a KDA layer
        of_state = [
            line.strip()[:120] for line in text.splitlines()
            for m in [re.search(
                r" = f32\[([\d,]+),32,128,128\]\S* (slice|dynamic-slice|"
                r"gather|scatter|copy|dynamic-update-slice)\(", line)]
            if m and math.prod(map(int, m.group(1).split(","))) >= B]
        assert not of_state, of_state
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in arrays)
    # (the slot axis of the small arrays is padded to whole tiles)
    assert held <= memory.alias_size_in_bytes <= 1.02 * held
    least = min(math.prod(a.shape) for a in arrays[:2])  # pool, KDA state
    moved = [
        line.strip()[:120] for line in big.as_text().splitlines()
        for m in [re.search(r" = \w+\[([\d,]+)\]\S* (copy|copy-start|pad|"
                            r"concatenate)\(", line)]
        if m and math.prod(map(int, m.group(1).split(","))) >= least]
    assert not moved, moved
    # (one row's scatter is a dynamic-update-slice that names the whole
    # array and writes a slot of it in place: a copy of it would show
    # in the temporaries, which must not grow with pages or slots)
    more = slots if slots == B else 2 * slots
    grown, twice = _kimi_step(one_chip, topo, monkeypatch, 4097, more, B, S)
    growth = sum(math.prod(a.shape) * a.dtype.itemsize for a in grown) - held
    assert twice.memory_analysis().temp_size_in_bytes \
        - memory.temp_size_in_bytes < 0.1 * growth
    if slots == B:      # and no loop over the rows writes the state back
        assert " while(" not in big.as_text()


def _k2_step(one_chip, topo, monkeypatch, B, S):
    """``FlaxModelAdapter``'s step for Kimi-K2 as the cell
    kimi_k2_7_code.serve_closed32_ctx8k runs it: the published widths,
    seven layers, 12 of 384 experts, 20,480 rows of the vocabulary, a
    pool of 18,433 pages and block tables of 576."""
    from ray_tpu.models.kimi_k2 import KimiK2Config
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    cfg = KimiK2Config(vocab_size=20480, num_hidden_layers=7,
                       experts_held=(0, 12), max_seq_len=9216)
    adapter = FlaxModelAdapter("kimi_k2", cfg, params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    adapter.bind_cache(PagedKVCache(2, 16))
    a = adapter._arrays["kv_pages"]
    pool = sds((a.shape[0], 18433, *a.shape[2:]), a.dtype)
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return params, pool, fn.lower(
            params, sds((B, S + 3 + adapter.nb_max), jnp.int32),
            *_last_tokens(sds, 18433, S), pool).compile()


# a compiled step's ``memory_analysis()`` by (model, B, S): the fits tests
# compile, the temporaries' test reads (it compiles only where it runs
# alone)
_MEMORY = {}


@pytest.mark.parametrize("B,S,temp_gib", [(32, 1, 0.05), (1, 8192, 3.7)],
                         ids=["decode_b32", "prefill_8192"])
def test_kimi_k2_step_fits_the_chip_at_the_timed_shapes(
        one_chip, topo, monkeypatch, B, S, temp_gib):
    """The two programs the cell times, compiled for the described v5e:
    9.03 GiB of weights and the 2.46 GiB pool as arguments, the pool
    donated and written in place, and temporaries that leave the whole
    under the chip's 15.75 GiB (``memory_analysis()``; the compiler
    refuses a program that does not fit). The decode step's routed
    experts are the Mosaic kernel (an expert of 88 MB at a 256-wide
    tile) and its attention another (``latent_attention_decode``: the
    step's temporaries are 22 MB where the gather to the padded context
    held 0.45 GiB); a prompt's attention is one (``latent_prefill_attention``, a
    call a layer, a head's 9,216 keys and values resident) and holds no
    [64, 512, 9216] of logits; its routed experts are the grouped kernel
    over three blocks of sorted rows at a time (the worst case's rows,
    2.75 GiB, would not fit). As read: 11.53 GiB of arguments, 2.85 GiB
    of temporaries, 14.37 of 15.75 GiB."""
    import math
    params, pool, step = _k2_step(one_chip, topo, monkeypatch, B, S)
    memory = _MEMORY["kimi_k2", B, S] = step.memory_analysis()
    gib = 2.0 ** 30
    held = sum(math.prod(s.shape) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert 9.0 < held / gib < 9.1
    # (a decode program's other donated argument: 32,768 tokens by row)
    assert memory.alias_size_in_bytes == math.prod(pool.shape) * 2 \
        + (4 * 32768 if S == 1 else 0)
    assert memory.temp_size_in_bytes < temp_gib * gib
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < 15.3 * gib
    text = step.as_text()
    assert text.count("tpu_custom_call") >= 13
    assert text.count("routed_experts_grouped") >= (0 if S == 1 else 6)
    assert "[64,512,9216]" not in text and "[1,64,512,9216]" not in text
    if S == 1:
        # a decode step's attention is the kernel over the pool as
        # stored, a call a layer: no row's table is gathered to its
        # 9,216 padded positions ([18432, 16, 640] or [32, 9216, ...])
        assert text.count("latent_attention_decode") >= 7
        assert "[18432,16,640]" not in text and "[32,9216," not in text


def _longcat_cell():
    """(configuration kwargs, decode slots, pages) of the cell
    longcat_flash_omni.serve_closed64_ctx2k, from its configuration
    file."""
    import json
    import os
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "configs", "longcat_flash_omni.json")) as f:
        data = json.load(f)
    kwargs = dict(data["model"]["kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    engine = data["serve"]["engine"]
    return kwargs, engine["max_running"], engine["num_blocks"]


def _longcat_step(one_chip, topo, monkeypatch, B, S):
    """``FlaxModelAdapter``'s step for LongCat-Flash as the cell
    longcat_flash_omni.serve_closed64_ctx2k runs it: the published
    widths, four double layers (eight cached sublayers), 16 of 512 real
    experts, 16,384 rows of the vocabulary, the pool and block tables
    its configuration file gives."""
    from ray_tpu.models.longcat_flash import LongcatFlashConfig
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kwargs, _, pages = _longcat_cell()
    cfg = LongcatFlashConfig(**kwargs)
    adapter = FlaxModelAdapter("longcat_flash", cfg, params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    adapter.bind_cache(PagedKVCache(2, 16))
    a = adapter._arrays["kv_pages"]
    pool = sds((a.shape[0], pages, *a.shape[2:]), a.dtype)
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return params, pool, fn.lower(
            params, sds((B, S + 3 + adapter.nb_max), jnp.int32),
            *_last_tokens(sds, pages, S), pool).compile()


@pytest.mark.parametrize("B,S,temp_gib", [(64, 1, 0.1), (1, 2048, 3.0)],
                         ids=["decode_b64", "prefill_2048"])
def test_longcat_flash_step_fits_the_chip_at_the_timed_shapes(
        one_chip, topo, monkeypatch, B, S, temp_gib):
    """The two programs the cell times, compiled for the described v5e:
    the weights and the eight-sublayer pool as arguments, the pool
    donated and written in place, and temporaries that leave the whole
    under the chip's 15.75 GiB (``memory_analysis()``). A decode step's
    attention is the kernel over the pool as stored, a call a SUBLAYER
    (eight), its routed experts the touched-experts kernel, a call a
    layer; a prompt's attention walks the keys in blocks inside one
    kernel, a call a sublayer (no [64, 512, 3072] of logits), and its
    routed product is the grouped kernel in a loop over the live chunks
    of 896 sorted rows (the worst case's 26,624 rows of 6144 held whole
    were 0.91 GiB, kept alive across the dense SwiGLU, the second
    attention and the second SwiGLU that the shortcut sets between its
    start and its use: 1.90 GiB of temporaries, now 0.82)."""
    import math

    from ray_tpu.serve.llm.model_runner import _pad_pow2
    params, pool, step = _longcat_step(one_chip, topo, monkeypatch, B, S)
    memory = _MEMORY["longcat_flash", B, S] = step.memory_analysis()
    gib = 2.0 ** 30
    held = sum(math.prod(s.shape) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"\n[longcat] B={B} S={S}: weights {held / gib:.3f} GiB, pool "
          f"{math.prod(pool.shape) * 2 / gib:.3f} GiB, arguments "
          f"{memory.argument_size_in_bytes / gib:.3f}, temporaries "
          f"{memory.temp_size_in_bytes / gib:.3f}, outputs "
          f"{memory.output_size_in_bytes / gib:.3f}, aliased "
          f"{memory.alias_size_in_bytes / gib:.3f}, total "
          f"{total / gib:.3f} GiB")
    assert 9.6 < held / gib < 9.7
    # (a decode program's other donated argument: the tokens by row)
    assert memory.alias_size_in_bytes == math.prod(pool.shape) * 2 \
        + (4 * _pad_pow2(pool.shape[1]) if S == 1 else 0)
    assert memory.temp_size_in_bytes < temp_gib * gib
    assert total < 15.3 * gib
    text = step.as_text()
    if S == 1:
        assert text.count("latent_attention_decode") >= 8
        assert text.count("routed_experts_touched") >= 4
        assert "[64,3072," not in text      # no gather to the padded table
    else:
        assert text.count("routed_experts_grouped") >= 4
        assert text.count("latent_prefill_attention") >= 8
        assert "[64,512,3072]" not in text
        assert memory.temp_size_in_bytes < 1.0 * gib


def _laguna_cell():
    """(decode slots, pages of the full layers' pools) of the cell
    laguna_xs_2.serve_closed64_ctx8k, from its configuration file."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "laguna_xs_2.json")) as f:
        engine = json.load(f)["serve"]["engine"]
    return engine["max_running"], engine["num_blocks"]


def _laguna_step(one_chip, topo, monkeypatch, B, S, window_pages=None):
    """``FlaxModelAdapter``'s step for Laguna as the cell
    laguna_xs_2.serve_closed64_ctx8k runs it: the published widths,
    layers 0-4 whole (all 256 experts, the whole vocabulary), the full
    layers' pools of ``num_blocks`` pages under tables of 576, the
    sliding layers' of ``window_pages`` (``max_running`` rings of 33 and
    the null page, as the engine sizes them) under rings of 33."""
    from ray_tpu.models.laguna import LagunaConfig
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    slots, full_pages = _laguna_cell()
    cfg = LagunaConfig(num_hidden_layers=5, max_seq_len=9216)
    adapter = FlaxModelAdapter("laguna", cfg, params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    adapter.bind_cache(PagedKVCache(2, 16, windows=adapter.page_windows,
                                    max_sequences=1))
    assert adapter._rings == {512: 33}
    pools = [sds((a.shape[0], (window_pages or slots * 33 + 1)
                  if "window" in name else full_pages, *a.shape[2:]),
                 a.dtype)
             for name, a in adapter._arrays.items()]
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return params, pools, fn.lower(
            params, sds((B, S + 3 + adapter.nb_max + 33), jnp.int32),
            *_last_tokens(sds, full_pages, S), *pools).compile()


@pytest.mark.parametrize("S,temp_gib", [(1, 0.05), (8192, 2.9)],
                         ids=["decode", "prefill_8192"])
def test_laguna_step_fits_the_chip_at_the_timed_shapes(
        one_chip, topo, monkeypatch, S, temp_gib):
    """The two programs the cell times (a decode step of every slot, a
    prompt of 8,192), compiled for the described v5e: 7.21 GiB of
    weights, the full layers' pools and the sliding layers' rings as
    arguments, all four pools donated and written in place, and
    temporaries that leave the whole under the chip's 15.75 GiB. A decode
    step's attention is the paged kernel in all five layers (groups of 6
    over the live pages, groups of 8 over the ring: no row's table is
    gathered) and its routed experts the Mosaic kernel over 256 experts;
    a prompt's attention is the blocked kernel, a call a layer, and
    holds no [heads, block, 8192] of logits; its routed experts are the
    grouped kernel, a call a layer over the worst case's 131,072 sorted
    rows held whole (1.5 GiB of bfloat16 rows and float32 results). As
    read: 12.10 GiB of arguments, 2.57 GiB of temporaries (1.54 before
    the rows were held whole), 14.67 of 15.75 GiB."""
    import math
    slots, full_pages = _laguna_cell()
    B = slots if S == 1 else 1
    params, pools, step = _laguna_step(one_chip, topo, monkeypatch, B, S)
    memory = _MEMORY["laguna", B, S] = step.memory_analysis()
    gib = 2.0 ** 30
    held = sum(math.prod(s.shape) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert 7.2 < held / gib < 7.23
    assert memory.alias_size_in_bytes == sum(
        math.prod(p.shape) * 2 for p in pools) \
        + (4 * 65536 if S == 1 else 0)      # (and the tokens by row)
    assert memory.temp_size_in_bytes < temp_gib * gib
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < (14.0 if S == 1 else 15.0) * gib
    text = step.as_text()
    assert "[64,512,8192]" not in text and "[48,512,8192]" not in text
    if S == 1:
        assert text.count("tpu_custom_call") >= 9       # 5 + 4
        assert f"[{full_pages - 1},16,1024]" not in text
        assert f"[{B},9216," not in text
        assert f"[{B},528," not in text     # nor a ring to its 528 rows
    else:
        assert text.count("tpu_custom_call") >= 9       # 5 + 4
        assert text.count("routed_experts_grouped") >= 4


def test_laguna_whole_context_pools_would_not_fit(one_chip, topo,
                                                  monkeypatch):
    """Were the sliding layers to keep every position as the full ones
    do (their pools as long as the full layers'), the prompt's program
    would not fit the chip's 15.75 GiB beside them: the compiler refuses
    it. The cell's sequences of 9,216 positions fit only because the
    window group holds a ring a sequence."""
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        _laguna_step(one_chip, topo, monkeypatch, 1, 8192,
                     window_pages=_laguna_cell()[1])


@pytest.mark.parametrize("H,window", [(48, None), (64, 512)],
                         ids=["full_g6", "window_g8"])
def test_paged_attention_decode_grouped_heads_and_ring(one_chip, H, window):
    """The paged kernel alone at the Laguna cell's shapes: a row a slot,
    8 key/value heads of 128, groups of 6 over tables of 576 pages and
    groups of 8 over a ring of 33."""
    B, full_pages = _laguna_cell()
    NB = 576 if window is None else 33
    P = full_pages if window is None else B * 33 + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    _compile(lambda q, k, v, bt, ln: A.paged_attention_decode(
        q, k, v, bt, ln, layer=1, window=window, interpret=False),
        sds((B, H, 128), jnp.bfloat16),
        sds((2, P, 16, 1024), jnp.bfloat16),
        sds((2, P, 16, 1024), jnp.bfloat16), sds((B, NB), jnp.int32),
        sds((B,), jnp.int32))


def _smallthinker_cell():
    """(engine, model kwargs) of the cell smallthinker_21b_a3b.
    serve_closed96_mix8k, from its configuration file."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "smallthinker_21b_a3b.json")) as f:
        cfg = json.load(f)
    return cfg["serve"]["engine"], cfg["model"]["kwargs"]


def _smallthinker_step(one_chip, topo, monkeypatch, B, S):
    """``FlaxModelAdapter``'s step for SmallThinker as the cell
    smallthinker_21b_a3b.serve_closed96_mix8k runs it: the published
    widths, layers 0-7 whole (all 64 experts, the whole vocabulary), the
    full layers' pools of ``num_blocks`` pages under tables of 576, the
    window layers' of ``window_blocks`` (stated, fewer than 96 whole
    rings) under rings of 257."""
    from benchmark.reference import smallthinker_glue as glue
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    engine, kwargs = _smallthinker_cell()
    cfg = glue.model_config({
        "factory": "ray_tpu.models.smallthinker:SmallThinkerConfig",
        "kwargs": kwargs})
    adapter = FlaxModelAdapter("smallthinker", cfg, params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    adapter.bind_cache(PagedKVCache(2, 16, windows=adapter.page_windows,
                                    window_blocks=2))
    assert adapter._rings == {4096: 257} and adapter.nb_max == 576
    pools = [sds((a.shape[0], engine["window_blocks"] if "window" in name
                  else engine["num_blocks"], *a.shape[2:]), a.dtype)
             for name, a in adapter._arrays.items()]
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return params, pools, fn.lower(
            params, sds((B, S + 3 + 576 + 257), jnp.int32),
            *_last_tokens(sds, engine["num_blocks"], S), *pools).compile()


# the 13 programs the cell's warm-up compiles: five prompts (one a step:
# max_prefill_tokens 256 under prompts of 257 and more), eight decode
# buckets to 128 rows (96 slots)
@pytest.mark.parametrize("B,S", [
    (1, 512), (1, 1024), (1, 2048), (1, 4096), (1, 8192),
    (1, 1), (2, 1), (4, 1), (8, 1), (16, 1), (32, 1), (64, 1), (128, 1)],
    ids=lambda v: str(v))
def test_smallthinker_programs_fit_the_chip_with_the_stated_pools(
        one_chip, topo, monkeypatch, B, S):
    """Each of the cell's 13 programs, compiled for the described v5e:
    7.39 GiB of weights, the full layers' pools (24,577 pages, 1.50 GiB)
    and the window layers' (18,433 pages where 96 whole rings would be
    24,673: 3.375 GiB) as arguments, all four pools donated and written
    in place, and temporaries that leave the whole under the chip's
    15.75 GiB. A decode step's attention is the paged kernel in all eight
    layers (groups of 7 over the live pages and over rings of 257: no
    row's table is gathered) and its routed experts the touched kernel
    over 64 ReGLU experts of three 256-wide tiles; a prompt's attention
    is the blocked kernel and its routed experts the grouped kernel (256
    rows and fewer: the touched one), a call a layer, over the worst
    case's 65,536 sorted rows held whole (0.94 GiB of bfloat16 rows and
    float32 results at 8,192 tokens). As read: 12.267 GiB of arguments;
    temporaries 0.010-0.017 GiB (1 to 128 rows) and 0.29, 0.44, 0.78,
    1.29 and 2.34 GiB (prompts of 512 to 8,192): 14.61 of 15.75 GiB at
    the most."""
    import math
    engine, _ = _smallthinker_cell()
    assert engine["max_running"] == 96 and engine["window_blocks"] \
        < 96 * 257 + 1
    params, pools, step = _smallthinker_step(one_chip, topo, monkeypatch,
                                             B, S)
    memory = _MEMORY["smallthinker", B, S] = step.memory_analysis()
    gib = 2.0 ** 30
    held = sum(math.prod(s.shape) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert 7.38 < held / gib < 7.40
    assert sum(math.prod(p.shape) * 2 for p in pools) / gib == 4.875 \
        + 8 * 32768 / gib       # (the null page of each layer's pool)
    assert memory.alias_size_in_bytes == sum(
        math.prod(p.shape) * 2 for p in pools) \
        + (4 * 32768 if S == 1 else 0)      # (and the tokens by row)
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"smallthinker b{B} s{S}: arguments "
          f"{memory.argument_size_in_bytes / gib:.3f} GiB, temporaries "
          f"{memory.temp_size_in_bytes / gib:.3f}, total {total / gib:.3f}")
    # (what was read, and a tenth)
    read = {1: 0.017, 512: 0.29, 1024: 0.44, 2048: 0.78, 4096: 1.29,
            8192: 2.34}[S]
    assert memory.temp_size_in_bytes < 1.1 * read * gib
    assert total < (12.4 if S == 1 else 14.9) * gib
    text = step.as_text()
    assert text.count("tpu_custom_call") >= 16          # 8 + 8
    if S == 1:
        assert text.count("routed_experts_touched") >= 8
        assert f"[{B},9216," not in text    # no table gathered whole
        assert f"[{B},4112," not in text    # nor a ring to its 4,112 rows
    else:
        assert text.count("routed_experts_grouped") >= 8
        assert f"[28,512,{S}]" not in text  # no [heads, block, S] logits


@pytest.mark.parametrize("model,read_gib", [("laguna", 2.57),
                                            ("kimi_k2", 2.85),
                                            ("smallthinker", 2.34)])
def test_a_prompts_temporaries_are_what_was_read(one_chip, topo, monkeypatch,
                                                 model, read_gib):
    """The (1, 8192) prefill programs' temporaries stay within a tenth
    of what ``memory_analysis()`` read when the grouped product went in
    (PR 40): a later product that quietly holds more rows (Kimi-K2's
    worst case in float32 is 1.97 GB) fails here and not in a cell,
    whose probe of ``correct`` has ~10 MB of the chip to spare."""
    memory = _MEMORY.get((model, 1, 8192))
    if memory is None:
        step = {"laguna": _laguna_step, "kimi_k2": _k2_step,
                "smallthinker": _smallthinker_step}[model](
            one_chip, topo, monkeypatch, 1, 8192)[2]
        memory = step.memory_analysis()
    assert memory.temp_size_in_bytes < 1.1 * read_gib * 2.0 ** 30


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_paged_attention_decode_groups_of_seven_and_a_ring_of_257(
        one_chip, window):
    """The paged kernel alone at the SmallThinker cell's shapes: 128 rows
    (96 slots padded), 4 key/value heads of 128, groups of 7 (28 heads in
    32-row matrices) over tables of 576 pages and over a ring of 257 (a
    prime: nothing but the ring rule's ``lp % 257`` divides it)."""
    engine, _ = _smallthinker_cell()
    NB = 576 if window is None else 257
    P = engine["num_blocks"] if window is None else engine["window_blocks"]
    L = 2 if window is None else 6

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    _compile(lambda q, k, v, bt, ln: A.paged_attention_decode(
        q, k, v, bt, ln, layer=1, window=window, interpret=False),
        sds((128, 28, 128), jnp.bfloat16),
        sds((L, P, 16, 512), jnp.bfloat16),
        sds((L, P, 16, 512), jnp.bfloat16), sds((128, NB), jnp.int32),
        sds((128,), jnp.int32))


@pytest.mark.parametrize("B,H,Hkv,P,NB,window,parent", [
    (16, 20, 20, 1025, 64, None, 32), (64, 48, 8, 36865, 576, None, 32),
    (64, 64, 8, 2113, 33, 512, 32), (128, 28, 4, 24577, 576, None, 128),
    (128, 28, 4, 18433, 257, 4096, 128)],
    ids=["gpt2-large", "laguna-full", "laguna-ring", "smallthinker-full",
         "smallthinker-ring"])
def test_paged_attention_decode_holds_no_more_copy_sites_than_it_did(
        B, H, Hkv, P, NB, window, parent):
    """What a decode bucket's first call pays to trace and lower the
    kernel grows with its copy sites (32 unrolled page copies a chunk
    took ``setup_s`` from 173-187 to 228-237 s, PR 43). The groups of a
    chunk and the pages of a group that is no run are loops inside the
    kernel: every cell's kernel starts a copy at 8 sites (a run's and a
    page's, K and V, at the first chunk and at the next one) and waits at
    2, where the parent's (``parent``: 2 x 2 x the pages of its chunk)
    had 32 at 2 KiB rows and 128 at 1 KiB; counted in the kernel's jaxpr,
    each equation of which Mosaic lowers once."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    D = 64 if H == Hkv else 128
    pool = sds((2, P, 16, Hkv * D), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v, bt, ln: (
        A.paged_attention_decode(q, k, v, bt, ln, layer=1, window=window,
                                 interpret=False)))(
        sds((B, H, D), jnp.bfloat16), pool, pool, sds((B, NB), jnp.int32),
        sds((B,), jnp.int32)))
    assert "paged_attention_decode" in text
    assert text.count("dma_start") == 8 <= parent
    assert text.count("dma_wait") == 2


def _mamba_step(sharding, R=256, slots=257, layers=26, N=16, d_in=5120):
    """The Mamba-1 decode kernel at the Jamba cell's shape: 256 rows over
    the state pool [26, 257, 16, 5120] float32 where it lies, the layer a
    traced scalar, each row's slot an array."""
    from ray_tpu.ops import ssm

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (lambda u, dt, B, C, A_, D, pool, layer, at:
            ssm.mamba_step_in_place(u, dt, B, C, A_, D, pool, layer, at)), (
        sds((R, d_in)), sds((R, d_in)), sds((R, N)), sds((R, N)),
        sds((N, d_in)), sds((d_in,)), sds((layers, slots, N, d_in)),
        sds((), jnp.int32), sds((R,), jnp.int32))


@pytest.mark.parametrize("R", [256, 8, 1])
def test_mamba_recurrence_decode(one_chip, R):
    """One Mosaic call; the pool is its own output (donated: no bytes
    beside the arguments), and nothing of the rows' state is copied,
    sliced or written back outside it. A full bucket, a bucket of one
    block of rows and a single row."""
    import math
    fn, args = _mamba_step(one_chip, R=R)
    with jax.default_matmul_precision("default"):
        big = jax.jit(fn, donate_argnums=(6,)).lower(*args).compile()
    text = big.as_text()
    assert text.count("tpu_custom_call") == 1 and "mamba_recurrence" in text
    memory = big.memory_analysis()
    assert memory.alias_size_in_bytes == math.prod(args[6].shape) * 4
    assert memory.temp_size_in_bytes < (1 << 20) + 3 * R * 5120 * 4
    assert not _moved(text, R * 16 * 5120)


def test_bare_mamba_kernel_is_refused_on_a_mesh(topo, monkeypatch):
    """As the other kernels': a Mosaic call cannot be partitioned, so
    ``mamba_decode_path`` says ``xla`` where a mesh of several devices is
    being traced for, and the bare call is refused there."""
    from ray_tpu.ops import ssm
    mesh, _ = _mesh4(topo)
    fn, args = _mamba_step(NamedSharding(mesh, P()), R=8, slots=9, layers=1)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(fn, *args)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert ssm.mamba_decode_path(args[6], 1) == "mamba_kernel"
    with A.attention_mesh(mesh):
        assert ssm.mamba_decode_path(args[6], 1) == "xla"


def _jamba_cell():
    """(engine, model kwargs) of the cell jamba2_3b.serve_closed256_chat,
    from its configuration file."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "jamba2_3b.json")) as f:
        cfg = json.load(f)
    return cfg["serve"]["engine"], cfg["model"]["kwargs"]


def _jamba_step(one_chip, topo, monkeypatch, B, S):
    """``FlaxModelAdapter``'s step for Jamba as the cell jamba2_3b.
    serve_closed256_chat runs it: the published widths, all 28 layers, the
    whole vocabulary, K and V pools of ``num_blocks`` pages for the two
    attention layers under tables of 96, 256 state slots and the null
    one."""
    from benchmark.reference import jamba_glue as glue
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    engine, kwargs = _jamba_cell()
    cfg = glue.model_config({
        "factory": "ray_tpu.models.jamba:JambaConfig", "kwargs": kwargs})
    adapter = FlaxModelAdapter("jamba", cfg, params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    adapter.bind_cache(PagedKVCache(2, 16))
    adapter.bind_state(1)
    adapter.state_slots = engine["max_running"]
    assert adapter.nb_max == 96
    arrays = [sds((a.shape[0], engine["num_blocks"]
                   if name in adapter._spec["pages"]
                   else engine["max_running"] + 1, *a.shape[2:]), a.dtype)
              for name, a in adapter._arrays.items()]
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return params, arrays, fn.lower(
            params, sds((B, S + 3 + 96), jnp.int32),
            *_last_tokens(sds, engine["num_blocks"], S), *arrays).compile()


@pytest.mark.parametrize("B,S,temp_gib", [
    (256, 1, 0.003), (8, 1, 0.257), (4, 512, 0.515), (8, 256, 0.554)],
    ids=["decode_b256_by_slot", "decode_b8", "prefill_4x512",
         "prefill_8x256"])
def test_jamba_programs_fit_the_chip_whole(one_chip, topo, monkeypatch, B, S,
                                           temp_gib):
    """The full decode bucket (rows in slot order), a narrow one (rows by
    ``slots``) and the two largest prefill programs of the cell, compiled
    for the described v5e: 5.65 GiB of weights (all 28 layers, the whole
    vocabulary), 2.04 GiB of Mamba state, 0.19 of convolution tails and
    0.375 of pages as arguments, all four arrays donated and written in
    place, temporaries as read (and a tenth): 8.82 of 15.75 GiB at the
    most. A decode step's recurrence is ONE Mosaic call a run of Mamba
    layers' loop (three runs) over the state pool where it lies, and its
    attention the paged kernel at groups of 20 in both attention layers;
    nothing of the state pool's size is copied, laid out anew, padded or
    stacked in any program, and the full decode bucket (rows in slot
    order: what a steady window runs) holds 3 MiB beside its arguments.
    (The convolution tails lie a tap's rows [slots, d_in] together, the
    chip's own choice for [26, 257, 3, 5120]; the programs that take rows
    by ``slots``, a narrow decode bucket and every prefill, lay them out
    anew once on the way in and once out, 0.25 GiB: PERF.md, PR 48.)"""
    import math
    engine, _ = _jamba_cell()
    params, arrays, step = _jamba_step(one_chip, topo, monkeypatch, B, S)
    memory = step.memory_analysis()
    gib = 2.0 ** 30
    held = sum(math.prod(s.shape) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert held == 6_063_769_088
    pools = sum(math.prod(a.shape) * a.dtype.itemsize for a in arrays)
    assert pools == 2_189_557_760 + 205_271_040 + 402_669_568
    assert pools <= memory.alias_size_in_bytes <= 1.02 * pools
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"jamba b{B} s{S}: arguments "
          f"{memory.argument_size_in_bytes / gib:.3f} GiB, temporaries "
          f"{memory.temp_size_in_bytes / gib:.3f}, total {total / gib:.3f}")
    assert memory.temp_size_in_bytes < 1.1 * temp_gib * gib + (1 << 20)
    assert total < 8.9 * gib
    assert total > 0.25 * 15.75 * gib       # the cell's floor, by far
    text = step.as_text()
    state = math.prod(arrays[2].shape)
    assert not _moved(text, state)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    if S == 1:
        assert sum("mamba_recurrence" in c for c in calls) == 3
        assert sum("paged_attention_decode" in c for c in calls) == 2
        assert f"[{B},1536," not in text    # no table gathered whole
    else:       # a prompt's scan is XLA's loop over its positions
        assert not any("mamba_recurrence" in c for c in calls)


def _lfm2_cell():
    """(engine, model kwargs) of the cell lfm2_8b_a1b.serve_closed256_1k,
    from its configuration file."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2_8b_a1b.json")) as f:
        cfg = json.load(f)
    return cfg["serve"]["engine"], cfg["model"]["kwargs"]


def _lfm2_step(one_chip, topo, monkeypatch, B, S):
    """``FlaxModelAdapter``'s step for LFM2 as the cell lfm2_8b_a1b.
    serve_closed256_1k runs it: the published widths, layers 0-15 with
    all 32 experts, the whole vocabulary, K and V pools of ``num_blocks``
    pages for the four attention layers under tables of 128, 256 tail
    slots and the null one."""
    from benchmark.reference import lfm2_glue as glue
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    engine, kwargs = _lfm2_cell()
    cfg = glue.model_config({
        "factory": "ray_tpu.models.lfm2:Lfm2Config", "kwargs": kwargs})
    adapter = FlaxModelAdapter("lfm2", cfg, params={})
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(adapter.model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32)))
    adapter.bind_cache(PagedKVCache(2, 16))
    adapter.bind_state(1)
    adapter.state_slots = engine["max_running"]
    assert adapter.nb_max == 128
    arrays = [sds((a.shape[0], engine["num_blocks"]
                   if name in adapter._spec["pages"]
                   else engine["max_running"] + 1, *a.shape[2:]), a.dtype)
              for name, a in adapter._arrays.items()]
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: topo.devices)
        fn = adapter._step_fn(B, S)
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    with jax.default_matmul_precision("default"):
        return params, arrays, fn.lower(
            params, sds((B, S + 3 + 128), jnp.int32),
            *_last_tokens(sds, engine["num_blocks"], S), *arrays).compile()


@pytest.mark.parametrize("B,S,temp_gib", [
    (256, 1, 0.067), (8, 1, 0.03), (4, 1024, 0.603), (8, 512, 0.411)],
    ids=["decode_b256_by_slot", "decode_b8", "prefill_4x1024",
         "prefill_8x512"])
def test_lfm2_programs_fit_the_chip_with_every_expert(one_chip, topo,
                                                      monkeypatch, B, S,
                                                      temp_gib):
    """The full decode bucket (rows in slot order), a narrow one (rows by
    ``slots``) and the two largest prefill programs of the cell, compiled
    for the described v5e: 10.06 GiB of weights (16 layers, all 32
    experts, the whole vocabulary), 2.25 GiB of pages and 24 MiB of tails
    as arguments, the three arrays donated and written in place,
    temporaries as read (and a tenth): 12.94 of 15.75 GiB at the most. A
    decode step's routed product is ONE Mosaic call a routed layer (14)
    over that layer's experts where they lie: nothing of an expert
    stack's size is copied, sliced or laid out anew in any program (a
    loop over stacked layers did copy it: models/lfm2.py). Its attention
    is the paged kernel at groups of 4 heads of 64 in all four attention
    layers, and no table is gathered whole."""
    import math
    engine, _ = _lfm2_cell()
    params, arrays, step = _lfm2_step(one_chip, topo, monkeypatch, B, S)
    memory = step.memory_analysis()
    gib = 2.0 ** 30
    held = sum(math.prod(s.shape) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert held == 10_800_230_144
    pools = sum(math.prod(a.shape) * a.dtype.itemsize for a in arrays)
    assert pools == 2_416_050_176 + 25_264_128
    assert pools <= memory.alias_size_in_bytes <= 1.02 * pools
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"lfm2 b{B} s{S}: arguments "
          f"{memory.argument_size_in_bytes / gib:.3f} GiB, temporaries "
          f"{memory.temp_size_in_bytes / gib:.3f}, total {total / gib:.3f}")
    assert memory.temp_size_in_bytes < 1.1 * temp_gib * gib + (1 << 20)
    assert total < 13.0 * gib
    assert total > 0.25 * 15.75 * gib       # the cell's floor, by far
    text = step.as_text()
    # an expert stack [32, 2048, 1792] is an argument and nothing else
    assert all(" parameter(" in line for line in _materialized(
        text, "bf16[32,2048,1792]", "bf16[32,1792,2048]"))
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    if S == 1:
        assert sum("routed_experts_" in c for c in calls) == 14
        assert sum("paged_attention_decode" in c for c in calls) == 4
        assert f"[{B},2048,512]" not in text    # no table gathered whole
    else:       # 4,096 padded tokens: sorted row blocks
        assert sum("routed_experts_grouped" in c for c in calls) == 14
        assert not any("paged_attention_decode" in c for c in calls)


@pytest.mark.parametrize("kind,config", [
    ("kimi_linear", "KimiLinearConfig"), ("kimi_k2", "KimiK2Config"),
    ("laguna", "LagunaConfig"),
    ("smallthinker", "SmallThinkerConfig"), ("jamba", "JambaConfig")])
def test_a_step_dispatched_ahead_runs_the_program_the_warm_up_compiled(
        kind, config):
    """The benchmark warms a decode bucket by a synchronous
    ``adapter.decode(seqs)`` with tokens from the host. A step dispatched
    ahead (``fetch=False``), with its tokens from the host or from the
    step in flight, is that very program: no entry more in ``_fns``,
    ``bucket_first_calls`` as it was, the jitted function traced once.
    (The tiny presets, on the CPU: nothing here depends on the chip.)"""
    import importlib

    import numpy as np

    from ray_tpu.serve.llm import PagedKVCache, SamplingParams
    from ray_tpu.serve.llm.engine import Sequence
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    glue = importlib.import_module(f"benchmark.reference.{kind}_glue")
    cfg = getattr(importlib.import_module(f"ray_tpu.models.{kind}"),
                  config).tiny()
    adapter = FlaxModelAdapter(kind, cfg, glue.init_for(cfg, 7))
    assert adapter.decode_ahead

    def served(ahead):
        cache = PagedKVCache(64, 8, windows=adapter.page_windows,
                             max_sequences=4)
        adapter.bind_cache(cache)
        if adapter.has_state:
            adapter.bind_state(4)
        seqs = []
        for i, n in enumerate((11, 5, 20)):
            cache.allocate(f"s{i}", n + 8)
            seqs.append(Sequence(f"s{i}", None, list(range(1, n + 1)),
                                 SamplingParams(max_new_tokens=8)))
        out = [adapter.prefill(seqs, tokens_only=True)]

        def commit(tokens):
            for s, t in zip(seqs, tokens):
                s.tokens.append(int(t))
        commit(out[0])
        out.append(adapter.decode(seqs, tokens_only=True))   # the warm-up's
        commit(out[1])
        if not ahead:
            for _ in range(2):
                out.append(adapter.decode(seqs, tokens_only=True))
                commit(out[-1])
            return np.stack(out)
        before = (adapter.bucket_first_calls, set(adapter._fns))
        first = adapter.decode(seqs, tokens_only=True, fetch=False)
        second = adapter.decode(seqs, tokens_only=True, fetch=False)
        out += [first.fetch(), second.fetch()]
        assert (adapter.bucket_first_calls, set(adapter._fns)) == before
        assert adapter._fns[4, 1, False]._cache_size() == 1
        assert [adapter._state[s.seq_id]["len"] for s in seqs] \
            == [len(s.prompt) + 3 for s in seqs]
        return np.stack(out)
    np.testing.assert_array_equal(served(True), served(False))


@pytest.mark.parametrize("kind,config", [
    ("kimi_linear", "KimiLinearConfig"), ("kimi_k2", "KimiK2Config"),
    ("laguna", "LagunaConfig"), ("longcat_flash", "LongcatFlashConfig"),
    ("smallthinker", "SmallThinkerConfig"), ("jamba", "JambaConfig"),
    ("lfm2", "Lfm2Config")])
def test_a_prompt_left_in_flight_runs_the_programs_the_warm_up_compiled(
        kind, config):
    """The benchmark warms a prefill bucket by a synchronous
    ``adapter.prefill(seqs)`` that returns logits. A prompt dispatched
    and left in flight (``tokens_only=True, fetch=False``), and the decode
    step behind it that feeds its rows' first tokens on the device, are
    the very programs that call and the warm-up's ``decode`` compiled,
    the copy behind a prompt's program (``llm_prefill_feed``) among them:
    no entry more in ``_fns``, ``bucket_first_calls`` as it was, each
    jitted function traced once; and the tokens are the synchronous
    order's. (The tiny presets, on the CPU: nothing here depends on the
    chip.)"""
    import importlib

    import numpy as np

    from ray_tpu.serve.llm import PagedKVCache, SamplingParams
    from ray_tpu.serve.llm.engine import Sequence
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    glue = importlib.import_module(f"benchmark.reference.{kind}_glue")
    cfg = getattr(importlib.import_module(f"ray_tpu.models.{kind}"),
                  config).tiny()
    adapter = FlaxModelAdapter(kind, cfg, glue.init_for(cfg, 7))

    def bound(tag):
        cache = PagedKVCache(64, 8, windows=adapter.page_windows,
                             max_sequences=4)
        adapter.bind_cache(cache)
        if adapter.has_state:
            adapter.bind_state(4)
        seqs = []
        for i, n in enumerate((11, 5, 20)):
            cache.allocate(f"{tag}{i}", n + 8)
            seqs.append(Sequence(f"{tag}{i}", None, list(range(1, n + 1)),
                                 SamplingParams(max_new_tokens=8)))
        return seqs

    def commit(seqs, tokens):
        for s, t in zip(seqs, tokens):
            s.tokens.append(int(t))

    # the warm-up's calls: logits from the prompts, then a decode step
    seqs = bound("w")
    commit(seqs, adapter.prefill(seqs).argmax(-1))
    want = [[s.tokens[0] for s in seqs]]
    for _ in range(2):
        want.append(adapter.decode(seqs, tokens_only=True))
        commit(seqs, want[-1])
    before = (adapter.bucket_first_calls, set(adapter._fns))
    sizes = {k: fn._cache_size() for k, fn in adapter._fns.items()}

    seqs = bound("a")
    prompt = adapter.prefill(seqs, tokens_only=True, fetch=False)
    assert prompt.at == {s.seq_id: adapter._feed_base + i
                         for i, s in enumerate(seqs)}
    first = adapter.decode(seqs, tokens_only=True, fetch=False)
    got = [prompt.fetch()]
    assert adapter._flying_prompt is None and adapter._flying is first
    second = adapter.decode(seqs, tokens_only=True, fetch=False)
    got += [first.fetch(), second.fetch()]
    assert (adapter.bucket_first_calls, set(adapter._fns)) == before
    assert {k: fn._cache_size() for k, fn in adapter._fns.items()} == sizes
    assert [adapter._state[s.seq_id]["len"] for s in seqs] \
        == [len(s.prompt) + 2 for s in seqs]
    np.testing.assert_array_equal(np.stack(got), np.stack(want))
