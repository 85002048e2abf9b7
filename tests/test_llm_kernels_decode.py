"""LLM serving, the numerics under the engine (docs/LLM_SERVING.md):
the paged-attention kernel vs the whole-kv reference, the page
allocator's exact admission, and incremental model decode vs the full
forward. Tier-1, CPU-only."""

import numpy as np
import pytest

from ray_tpu.serve.llm import PagedKVCache
from ray_tpu.serve.llm.kv_cache import OutOfKVBlocksError


# ------------------------------------------------------ kernel numerics


# lengths against pages of 16 tokens and groups of 8 pages (128 tokens): a
# padding row, one token, a whole page, a page and one, an end mid-group,
# whole groups, the full table
_LENGTHS = [0, 1, 16, 17, 100, 128, 200, 256]


def _tables(kind, held, NB, rng):
    """[rows, NB] block tables of ``held[b]`` pages a row (the rest the
    null page) from a pool of ``_pool_pages(held)`` pages, by ``kind``:
    ``shuffled`` (no two neighbours consecutive but by chance),
    ``one-run`` (a row's pages ascending and consecutive: what a fresh
    interval gives), ``broken-runs`` (runs of 3 to 11 pages in any order:
    a break inside most groups of 8), ``descending`` (consecutive, the
    wrong way round), ``shared-prefix`` (every row's first three pages are
    the SAME pages, from the pool's top, then a run of its own: not
    sorted), ``swapped-inside`` (one run but for the second and third
    entry of every group of 8, which change places: the group's two ends
    still differ by 7)."""
    tables = np.zeros((len(held), NB), np.int32)
    at = 1
    shared = list(range(1 + sum(held), _pool_pages(held)))
    for row, n in zip(tables, held):
        own = list(range(at, at + n))
        at += n
        if kind == "descending":
            own = own[::-1]
        elif kind == "broken-runs":
            cuts, runs = 0, []
            while cuts < n:
                step = int(rng.integers(3, 12))
                runs.append(own[cuts:cuts + step])
                cuts += step
            own = [p for i in rng.permutation(len(runs)) for p in runs[i]]
        elif kind == "shared-prefix":
            own = (shared + own)[:n]
        elif kind == "swapped-inside":
            for i in range(1, n - 1, 8):
                own[i], own[i + 1] = own[i + 1], own[i]
        row[:n] = own
    if kind == "shuffled":
        pages = iter(1 + rng.permutation(sum(held)))
        for row, n in zip(tables, held):
            row[:n] = [next(pages) for _ in range(n)]
    return tables


def _pool_pages(held):
    """The null page, every row's own pages and three shared ones."""
    return 1 + sum(held) + 3


_TABLE_KINDS = ["shuffled", "one-run", "broken-runs", "descending",
                "shared-prefix", "swapped-inside"]


@pytest.mark.parametrize("tables", ["shuffled", "one-run", "shared-prefix"])
@pytest.mark.parametrize("layer", [0, 2], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("H,Hkv,D", [(4, 4, 64), (8, 2, 128)],
                         ids=["mha-64", "gqa-128"])
def test_paged_attention_matches_whole_kv_reference(H, Hkv, D, dtype, tol,
                                                    layer, tables):
    """The Pallas paged-decode kernel (interpret mode on CPU) over the
    serving pool [L, P, bs, Hkv*D], the paged gather reference, and the
    contiguous whole-kv decode path must agree on the same cache
    contents: GPT-2-like heads (two to a lane tile) and Llama-like
    (grouped, a tile each), block tables whose unused entries are the
    null page, a row with no token (finite, and nothing else); tables of
    shuffled pages (a copy a page), of one ascending run a row (one copy
    a group of 8) and with a shared prefix."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    rng = np.random.default_rng(0)
    L, bs, NB = 3, 16, 16
    B, C = len(_LENGTHS), Hkv * D
    held = [-(-n // bs) for n in _LENGTHS]
    P = _pool_pages(held)
    dt = jnp.dtype(dtype)
    lengths = jnp.asarray(_LENGTHS, jnp.int32)
    k_pages = jnp.asarray(rng.normal(size=(L, P, bs, C)), dt)
    v_pages = jnp.asarray(rng.normal(size=(L, P, bs, C)), dt)
    bt = jnp.asarray(_tables(tables, held, NB, rng))
    q = jnp.asarray(rng.normal(size=(B, H, D)), dt)

    ref = A.paged_attention_reference(q, k_pages, v_pages, bt, lengths,
                                      layer=layer)
    kernel = A.paged_attention_decode(q, k_pages, v_pages, bt, lengths,
                                      layer=jnp.int32(layer),
                                      interpret=True)
    assert kernel.shape == (B, H, D) and kernel.dtype == dt
    live = np.asarray(lengths) > 0
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (kernel, ref))
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    assert np.all(got[~live] == 0)

    # contiguous whole-kv path over the SAME logical cache
    def whole(pages):
        return A.paged_gather(pages, bt, layer).reshape(B, -1, Hkv, D)
    cont = A.decode_attention(q[:, :, None, :], whole(k_pages),
                              whole(v_pages), lengths)[:, :, 0, :]
    np.testing.assert_allclose(
        got[live], np.asarray(cont.astype(jnp.float32))[live],
        rtol=tol, atol=tol)


@pytest.mark.parametrize("G", [1, 6, 7, 8])
@pytest.mark.parametrize("window", [None, 240], ids=["full", "ring"])
@pytest.mark.parametrize("tables", _TABLE_KINDS)
def test_paged_attention_copies_runs_as_the_tables_allow(tables, window, G):
    """Any table is right, only slower: the kernel interpreted against the
    gather, over pools of 1 KiB rows (chunks of 32 pages, four groups of
    8; over the ring of 16 pages two), at 1, 6, 7 and 8 query heads a
    key/value head. The rows: none, one token, whole chunks, a last chunk
    that is mostly dead pages (its dead groups are not copied), the
    table's end; over the ring also a short ring padded with the null
    page, exactly a window, and rings that wrap, inside a group of a
    table of one run too. A table of one run, runs broken inside a
    group, a descending table, a shared prefix (not sorted), a group
    whose two ends differ by 7 and which is still no run, shuffled
    pages."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    rng = np.random.default_rng(G)
    Hkv, D, bs = 2, 128, 16
    C = Hkv * D
    assert A.paged_chunk_tokens(C * 4) == 512
    if window is None:
        NB, lengths = 40, [0, 1, 128, 129, 300, 513, 530, 640]
        held = [min(NB, -(-n // bs) + 3) for n in lengths]
    else:
        NB, lengths = window // bs + 1, [0, 5, 100, 240, 256, 300, 513, 1000]
        held = [min(NB, -(-n // bs)) for n in lengths]
    held[0] = 0
    B, P = len(lengths), _pool_pages(held)
    k_pages = jnp.asarray(rng.normal(size=(2, P, bs, C)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(2, P, bs, C)), jnp.float32)
    bt = _tables(tables, held, NB, rng)
    if tables == "swapped-inside":
        assert bt[-1, 7] - bt[-1, 0] == 7 and bt[-1, 1] - bt[-1, 0] == 2
    args = (jnp.asarray(rng.normal(size=(B, Hkv * G, D)), jnp.float32),
            k_pages, v_pages, jnp.asarray(bt),
            jnp.asarray(lengths, jnp.int32))
    got = np.asarray(A.paged_attention_decode(
        *args, layer=1, window=window, interpret=True))
    want = np.asarray(A.paged_attention_reference(
        *args, layer=1, window=window))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    assert np.all(got[~live] == 0)


def test_the_kernels_groups_are_the_allocators():
    """The kernel copies a group of ``PAGED_RUN_PAGES`` table entries at
    once where they are consecutive; the allocator counts the pages of
    such groups by the same number."""
    from ray_tpu.ops import attention as A
    from ray_tpu.serve.llm import kv_cache
    assert A.PAGED_RUN_PAGES == kv_cache.RUN_PAGES == 8
    assert kv_cache.run_pages(list(range(5, 25))) == 16
    assert kv_cache.run_pages(list(range(5, 12))) == 0
    assert kv_cache.run_pages([1, 2, 3, 4, 6, 5, 7, 8]) == 0
    assert kv_cache.run_pages([9, 2, 3, 4, 5, 6, 7, 16]) == 0
    assert kv_cache.run_pages(list(range(20, 4, -1))) == 0


def test_latent_run_pages_share_reads_the_latent_kernels_steps():
    """The benchmark's ``latent_run_pages_share.serve``: the allocator's
    two counts on the window's decode steps that ran the latent kernel,
    and on no other step (a gather's, a step outside the window); nothing
    where no such step says them."""
    import importlib.util
    import os
    import types

    from benchmark.harness import cells
    spec = importlib.util.spec_from_file_location(
        "latent_run_share", os.path.join(
            cells.BENCH_DIR, "layer_metrics",
            "latent_run_pages_share.serve.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def step(t0, attention, run, held, name="llm.step.decode"):
        attrs = {"attention": attention, "kv_run_pages": run,
                 "kv_table_pages": held}
        return {"name": "llm.step", "t0": t0, "t1": t0 + 0.5, "children": [
            {"name": name, "t0": t0, "t1": t0 + 0.4, "children": [
                {"name": "runner.dispatch", "t0": t0, "t1": t0 + 0.1,
                 "attrs": attrs, "children": []}]}]}
    log = [step(0, "latent_kernel", 1000, 1000),        # before the window
           step(10, "latent_kernel", 560, 576),
           step(11, "latent_kernel", 376, 384),
           step(12, "gather", 0, 64),
           step(13, "latent_kernel", 8, 8, name="llm.step.prefill")]
    obs = types.SimpleNamespace(engine_metrics={"step_log": log},
                                t0=9.0, t1=20.0)
    assert reader.read(obs) == 100.0 * (560 + 376) / (576 + 384)
    obs.engine_metrics = {"step_log": [log[3]]}
    assert reader.read(obs) is None
    obs.engine_metrics = {}
    assert reader.read(obs) is None


def test_cached_attention_takes_the_kernel_by_what_it_sees(monkeypatch):
    """One token a row over the serving pool on a chip: the kernel, and
    its result is the gather's; a window of tokens, a pool the kernel
    cannot take, a mesh of several devices, the CPU: the gather."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    pool = jnp.zeros((2, 9, 16, 2 * 64), jnp.float32)
    assert A.paged_decode_path(2, 64, pool, 1) == "gather"      # the CPU
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert A.paged_decode_path(2, 64, pool, 1) == "paged_kernel"
    assert A.paged_decode_path(4, 64, pool, 1) == "paged_kernel"    # GQA
    assert A.paged_decode_path(2, 64, pool, 8) == "gather"
    assert A.paged_decode_path(2, 64, pool, 1, layer=None) == "gather"
    assert A.paged_decode_path(2, 32, pool[..., :64], 1) == "gather"
    assert A.paged_decode_path(2, 64, pool[:, :, :4], 1) == "gather"
    from ray_tpu.parallel.mesh import MeshSpec
    with A.attention_mesh(MeshSpec(dp=2).build(jax.devices()[:2])):
        assert A.paged_decode_path(2, 64, pool, 1) == "gather"

    rng = np.random.RandomState(3)
    B, H, D = 3, 2, 64
    cache = {"k_pages": jnp.asarray(rng.randn(*pool.shape), jnp.float32),
             "v_pages": jnp.asarray(rng.randn(*pool.shape), jnp.float32),
             "block_tables": jnp.asarray([[1, 2, 0], [3, 0, 0], [0, 0, 0]],
                                         jnp.int32)}
    q, k, v = (jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
               for _ in range(3))
    lengths = jnp.asarray([20, 5, 0], jnp.int32)
    valid = jnp.asarray([[True], [True], [False]])
    calls = []
    kernel = A.paged_attention_decode

    def interpreted(*a, **kw):
        calls.append(kw["layer"])
        return kernel(*a, **kw, interpret=True)
    monkeypatch.setattr(A, "paged_attention_decode", interpreted)
    got, new = A.cached_attention(q, k, v, cache, lengths, valid=valid,
                                  layer=1)
    assert calls == [1]
    monkeypatch.setattr(A, "_use_pallas", lambda: False)
    want, new_ref = A.cached_attention(q, k, v, cache, lengths, valid=valid,
                                       layer=1)
    assert calls == [1]
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new["k_pages"]),
                                  np.asarray(new_ref["k_pages"]))


# ------------------------------------------- latent (MLA) decode kernel

_R, _DR, _W, _DN, _DV = 512, 64, 640, 16, 16      # a row as both Kimis'


def _latent_case(lengths, H, dtype, seed=0, tables=None, L=2, held=None):
    """A pool [L, P, 16, W] with the rows' pages scattered in it (page 0
    the null page, pages no table reaches beyond the last), queries and
    ``kv_b``; tables of 80 pages whose unused entries are the null page
    (``held``: the pages a row of ``tables`` holds, its unwritten ones
    among them)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    B = len(lengths)
    need = [-(-n // 16) for n in lengths]
    P = _pool_pages(need if held is None else held)
    dt = jnp.dtype(dtype)
    pool = rng.randn(L, P, 16, _W).astype(np.float32)
    pool[..., _R + _DR:] = 0
    if tables is None:
        tables = np.zeros((B, 80), np.int32)
        pages = iter(rng.permutation(np.arange(1, 1 + sum(need))))
        for row, n in zip(tables, need):
            row[:n] = [next(pages) for _ in range(n)]
    return {
        "pool": jnp.asarray(pool, dt), "tables": jnp.asarray(tables),
        "lengths": jnp.asarray(lengths, jnp.int32),
        "q_nope": jnp.asarray(rng.randn(B, 1, H, _DN), dt),
        "q_rope": jnp.asarray(rng.randn(B, 1, H, _DR), dt),
        "w_kvb": jnp.asarray(rng.randn(_R, H, _DN + _DV) * _R ** -0.5, dt)}


def _latent_kernel(case, layer, pool=None):
    """As ``MLAMixer`` calls it: (q_n W_uk | q_r | 0) against the pool,
    the sums through W_uv."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    B, _, H, _ = case["q_nope"].shape
    dt = case["pool"].dtype
    q_abs = jnp.concatenate([
        jnp.einsum("bhd,rhd->bhr", case["q_nope"][:, 0],
                   case["w_kvb"][..., :_DN]), case["q_rope"][:, 0],
        jnp.zeros((B, H, _W - _R - _DR), dt)], axis=-1)
    out = A.latent_attention_decode(
        q_abs, case["pool"] if pool is None else pool, case["tables"],
        case["lengths"], rank=_R, layer=layer,
        sm_scale=(_DN + _DR) ** -0.5, interpret=True)
    assert out.shape == (B, H, _R) and out.dtype == jnp.float32
    return jnp.einsum("bhr,rhd->bhd", out.astype(dt),
                      case["w_kvb"][..., _DN:]), out


def _latent_reference(case, layer):
    from ray_tpu.ops import attention as A
    latent = A.paged_gather(case["pool"], case["tables"],
                            layer)[..., :_R + _DR]
    return A.latent_attention(
        case["q_nope"], case["q_rope"], latent, case["w_kvb"],
        case["lengths"][:, None] - 1, v_dim=_DV, absorbed=True)[:, 0]


def _chunk_tokens(dtype):
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    return A._LATENT_CHUNK_BYTES // (_W * jnp.dtype(dtype).itemsize)


def _boundaries(dtype):
    # against pages of 16 tokens and a chunk of T: a padding row, one
    # token, a whole page, a page and one, a chunk less one, a chunk, a
    # chunk and one, the full table
    T = _chunk_tokens(dtype)
    return [0, 1, 16, 17, T - 1, T, T + 1, 1280]


def _poisoned(case, held):
    """The case's pool with NaN in the null page and in every page no
    table reaches (``held``: the entries a row's table holds): a copy
    from outside a row's own pages would end in its sums."""
    import jax.numpy as jnp
    reached = np.zeros(case["pool"].shape[1], bool)
    for row, n in zip(np.asarray(case["tables"]), held):
        reached[row[:n]] = True
    assert not reached[0] and (~reached).sum() >= 1
    return jnp.where(reached[None, :, None, None], case["pool"], jnp.nan)


@pytest.mark.parametrize("case", [
    "h32-bfloat16", "h64-bfloat16", "h32-float32", "h64-float32",
    "traced-layer", "descending-table", "empty-first-and-last",
    "poisoned-pages"] + [f"{kind}-h{H}" for kind in _TABLE_KINDS
                         for H in (32, 64)])
def test_latent_attention_decode_matches_the_gather(case):
    """The Pallas latent-decode kernel (interpret mode on the CPU) over
    the latent pool as stored against ``paged_gather`` +
    ``latent_attention(absorbed=True)`` on the same pool. By table kind
    (``_tables``: any table is right, only slower), at 32 and 64 heads:
    rows of no token, of one page, of exactly a group of 8 pages, of
    exactly a chunk, of a chunk and one token, each holding three pages
    it has yet to write (a run goes on past a row's last live page), the
    null page and every page outside the tables NaN."""
    import jax
    import jax.numpy as jnp
    H = 64 if case.startswith("h64") or case.endswith("-h64") else 32
    kind = case.rsplit("-h", 1)[0]
    dtype = "float32" if case.endswith("float32") or kind in _TABLE_KINDS \
        else "bfloat16"
    tol = 1e-5 if dtype == "float32" else 2e-2
    lengths, tables, layers, held = _boundaries(dtype), None, [1], None
    if kind in _TABLE_KINDS:
        T = _chunk_tokens(dtype)
        lengths = [0, 16, 128, T, T + 1]
        held = [0] + [-(-n // 16) + 3 for n in lengths[1:]]
        tables = _tables(kind, held, 80, np.random.default_rng(H))
        if kind == "swapped-inside":
            assert tables[-1, 7] - tables[-1, 0] == 7 \
                and tables[-1, 1] - tables[-1, 0] == 2
    elif case == "descending-table":
        lengths = [1280, 100]
        tables = np.zeros((2, 80), np.int32)
        tables[0] = np.arange(87, 7, -1)
        tables[1, :7] = np.arange(7, 0, -1)
    elif case == "empty-first-and-last":
        lengths = [0, 0, 300, 0, _chunk_tokens(dtype) + 1, 0]
    elif case == "traced-layer":
        lengths, layers = [0, 17, 1030], [0, 1, 2]
    c = _latent_case(lengths, H, dtype, seed=len(case), tables=tables,
                     L=max(layers) + 1, held=held)
    live = np.asarray(lengths) > 0
    if case == "traced-layer":
        # the layer a loop's counter, as a model that loops over stacked
        # blocks would hand it
        got = jax.lax.fori_loop(
            0, len(layers), lambda i, acc: acc.at[i].set(
                _latent_kernel(c, i)[0]),
            jnp.zeros((len(layers), len(lengths), H, _DV),
                      c["pool"].dtype))
    elif case == "poisoned-pages" or held is not None:
        # (without ``held``: a row's table holds its live pages alone, so
        # a copy past a row's live pages would end in its sums)
        poisoned = _poisoned(c, held or [-(-n // 16) for n in lengths])
        got, raw = _latent_kernel(c, layers[0], pool=poisoned)
        assert np.all(np.isfinite(np.asarray(raw)))
        got = got[None]
    else:
        got = _latent_kernel(c, layers[0])[0][None]
    for i, layer in enumerate(layers):
        want = np.asarray(_latent_reference(c, layer).astype(jnp.float32))
        have = np.asarray(got[i].astype(jnp.float32))
        np.testing.assert_allclose(have[live], want[live], rtol=tol,
                                   atol=tol)
        assert np.all(have[~live] == 0)


def test_latent_decode_keeps_a_skipped_groups_buffer_rows_out_of_the_sums():
    """A group wholly past a row's last live page is never copied, so its
    rows of the buffer hold what was there before: NaN when the kernel
    starts (the interpreter fills a scratch buffer so, as a chip's VMEM
    may hold anything), and after a row of whole chunks that row's
    latents, here 1e30 a value. A first row of three tokens (seven of its
    chunk's eight groups are skipped) and a row of one page behind a row
    of a chunk and a page must come out finite and the gather's: the
    buffers are zeroed before the first row, and a masked position's
    probability is exactly 0."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def peek(o_ref, scratch):
        o_ref[...] = scratch[...]
    raw = pl.pallas_call(
        peek, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        interpret=True)()
    assert np.all(np.isnan(np.asarray(raw)))

    T = _chunk_tokens("float32")
    lengths = [3, T + 16, 16]
    c = _latent_case(lengths, 32, "float32", seed=11)
    # the long row's pages from its second group on: huge, finite
    pool = np.asarray(c["pool"]).copy()
    pool[:, np.asarray(c["tables"])[1, 8:T // 16 + 1]] *= 1e30
    c["pool"] = jnp.asarray(pool)
    got, raw = _latent_kernel(c, 1)
    assert np.all(np.isfinite(np.asarray(raw)))
    want = np.asarray(_latent_reference(c, 1))
    np.testing.assert_allclose(np.asarray(got)[[0, 2]], want[[0, 2]],
                               rtol=1e-5, atol=1e-5)


def test_mla_mixer_takes_the_latent_kernel_by_what_it_sees(monkeypatch):
    """``latent_decode_path`` on each side of each of its conditions,
    and ``MLAMixer`` over pages with it: one token a row on a chip is
    the kernel, and its result is the gather's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.kimi_k2 import KimiK2Config
    from ray_tpu.models.mla import MLAMixer
    from ray_tpu.ops import attention as A
    pool = jnp.zeros((2, 9, 16, 256), jnp.bfloat16)
    assert A.latent_decode_path(pool, 128, 1) == "gather"       # the CPU
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert A.latent_decode_path(pool, 128, 1) == "latent_kernel"
    assert A.latent_decode_path(pool, 128, 1, jnp.int32(1)) \
        == "latent_kernel"
    assert A.latent_decode_path(pool.astype(jnp.float32)[:, :, :8], 128,
                                1) == "latent_kernel"
    assert A.latent_decode_path(None, 128, 1) == "gather"   # no pages
    assert A.latent_decode_path(pool, 128, 8) == "gather"   # a window
    assert A.latent_decode_path(pool, 128, 1, layer=None) == "gather"
    assert A.latent_decode_path(pool[..., :192], 128, 1) == "gather"
    assert A.latent_decode_path(pool, 96, 1) == "gather"
    assert A.latent_decode_path(pool[:, :, :8], 128, 1) == "gather"
    from ray_tpu.parallel.mesh import MeshSpec
    with A.attention_mesh(MeshSpec(dp=2).build(jax.devices()[:2])):
        assert A.latent_decode_path(pool, 128, 1) == "gather"

    cfg = KimiK2Config.tiny(kv_lora_rank=128, qk_rope_head_dim=8)
    mixer = MLAMixer(cfg)
    rng = np.random.RandomState(5)
    B, D = 3, cfg.hidden_size
    x = jnp.asarray(rng.randn(B, 1, D), jnp.float32)
    pages = jnp.asarray(rng.randn(2, 9, 16, 256), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 0, 0], [0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([19, 4, 0], jnp.int32)
    valid = jnp.asarray([[True], [True], [False]])
    params = mixer.init(jax.random.PRNGKey(0), x)
    calls = []
    kernel = A.latent_attention_decode

    def interpreted(*a, **kw):
        calls.append(kw["layer"])
        return kernel(*a, **kw, interpret=True)
    monkeypatch.setattr(A, "latent_attention_decode", interpreted)
    args = dict(pages=pages, block_tables=tables, seq_lengths=lengths,
                valid=valid, layer=1)
    got, new = mixer.apply(params, x, **args)
    assert calls == [1]
    monkeypatch.setattr(A, "_use_pallas", lambda: False)
    want, new_ref = mixer.apply(params, x, **args)
    assert calls == [1]
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(new_ref))
    # a window of tokens gathers, on the chip too
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    mixer.apply(params, jnp.concatenate([x, x], 1), **dict(
        args, valid=jnp.concatenate([valid, valid], 1)))
    assert calls == [1]
    # and the adapter's dispatch spans will say what the mixer does
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    adapter = FlaxModelAdapter("kimi_k2", cfg, params={})
    adapter.bind_cache(PagedKVCache(8, 16))
    assert adapter._decode_attention == "latent_kernel"
    monkeypatch.setattr(A, "_use_pallas", lambda: False)
    adapter.bind_cache(PagedKVCache(8, 16))
    assert adapter._decode_attention == "gather"


def test_paged_kv_allocator_exact_admission():
    c = PagedKVCache(num_blocks=8, block_size=4)   # 7 usable pages
    assert c.blocks_for(9) == 3
    t1 = c.allocate("a", 9)             # 3 pages
    assert 0 not in t1                  # page 0 reserved (null page)
    assert c.can_allocate(16)           # 4 pages left
    assert not c.can_allocate(17)       # 5 needed, 4 free
    with pytest.raises(OutOfKVBlocksError):
        c.allocate("b", 17)
    assert abs(c.occupancy() - 3 / 7) < 1e-9
    assert c.free("a") == 3
    assert c.occupancy() == 0.0
    assert c.free("a") == 0             # double free is a no-op


def test_the_allocator_hands_out_ascending_runs_and_joins_them_again():
    """The order the free intervals pin: a fresh pool gives 1, 2, 3, ...;
    a need goes to the smallest interval that holds it (an exact fit
    whole, the long intervals kept for the long needs), else to the
    longest intervals whole and then the smallest that holds the rest,
    each interval's pages ascending, the intervals by address; a freed
    table joins its neighbours, a page dropped alone too; copy-on-write
    takes its page from the smallest interval."""
    c = PagedKVCache(num_blocks=41, block_size=1)    # pages 1..40
    assert c.allocate("a", 10) == list(range(1, 11))
    assert c.allocate("b", 4) == list(range(11, 15))
    assert c.allocate("c", 6) == list(range(15, 21))
    assert c.allocate("d", 3) == list(range(21, 24))
    assert c.stats()["kv_run_pages_share"] == 8 / 23    # a's first group
    c.free("b")                       # free: 11..14, 24..40
    c.free("d")                       # free: 11..14, 21..40 (joined)
    assert c.allocate("e", 4) == [11, 12, 13, 14]       # the exact fit
    c.free("e")
    assert c.allocate("f", 3) == [11, 12, 13]   # the smallest that holds it
    assert c.allocate("g", 2) == [21, 22]       # 14 alone does not
    c.free("a")                       # free: 1..10, 14, 23..40
    # 25 pages: no interval holds them; the longest whole (23..40), and
    # the rest from the smallest that holds 7 (1..10), by address
    assert c.allocate("h", 25) == list(range(1, 8)) + list(range(23, 41))
    assert c.free_blocks() == 4                 # 8, 9, 10 and 14
    c.allocate_with_prefix("i", 3, c.block_table("h")[:2])   # one fresh
    assert c.block_table("i")[2] == 14          # from the smallest interval
    assert c.copy_on_write("i", 0) == (1, 8)
    for seq in "cfghi":
        c.free(seq)
    assert sorted(c._free) == list(range(1, 41))
    assert c.allocate("z", 40) == list(range(1, 41))    # one interval again
    assert c.decref([7]) == 1 and c.decref([5, 6]) == 2
    assert c.allocate_with_prefix("y", 4, [1]) == [1, 5, 6, 7]


def _check_allocator(cache, tables, extra):
    """Every page but the null one is free or referenced, as often as
    tables and other holders (``extra``: page -> count) name it; no ring
    page is held twice; the counts are the intervals'."""
    want = dict(extra)
    for t in tables.values():
        for p in t:
            want[p] = want.get(p, 0) + 1
    free = list(cache._free)
    assert len(free) == len(set(free)) == cache.free_blocks()
    assert not set(free) & set(want) and 0 not in want and 0 not in free
    assert sorted(free + list(want)) == list(range(1, cache.num_blocks))
    assert all(cache.ref_count(p) == n for p, n in want.items())
    for w in cache.windows:
        g = cache._rings[w]
        held = [p for t in g.tables.values() for p in t]
        assert len(held) == len(set(held)) and 0 not in held
        assert sorted(held + list(g.free)) == list(range(1, g.num_blocks))
        assert len(g.free) == g.num_blocks - 1 - len(held)


@pytest.mark.parametrize("windowed", [False, True], ids=["plain", "rings"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_allocator_keeps_its_books_under_random_traffic(seed, windowed):
    """Random admissions, releases and (without a window group) shared
    prefixes, copies on write and references taken and dropped by a
    prefix cache: no page is ever held twice, ``can_allocate`` is true
    exactly where every group's free count suffices and ``allocate``
    then never raises (and raises, taking nothing, where it is false),
    and when everything is released every page is back in one interval a
    group."""
    from ray_tpu.serve.llm.kv_cache import run_pages
    rng = np.random.default_rng(seed)
    bs = 4
    cache = PagedKVCache(97, bs, windows=(48,) if windowed else (),
                         max_sequences=6, window_blocks=41)
    tables, extra, n = {}, {}, 0
    for _ in range(600):
        op = rng.choice(["admit", "admit", "release", "prefix", "cow",
                         "incref", "decref"])
        if op == "admit" or (windowed and op == "prefix"):
            tokens = int(rng.integers(1, 160))
            need = cache.blocks_for(tokens)
            fits = cache.free_blocks() >= need and all(
                cache.ring_need(w, tokens)
                <= cache.stats()["kv_window_groups"][w]["blocks_total"]
                - cache.stats()["kv_window_groups"][w]["blocks_used"]
                for w in cache.windows)
            assert cache.can_allocate(tokens) == fits
            before = (cache.free_blocks(), cache.stats())
            if fits:
                tables[f"s{n}"] = cache.allocate(f"s{n}", tokens)
                assert len(tables[f"s{n}"]) == need
                for w in cache.windows:
                    assert len(cache.ring_table(f"s{n}", w)) \
                        == cache.ring_need(w, tokens)
            else:
                with pytest.raises(OutOfKVBlocksError):
                    cache.allocate(f"s{n}", tokens)
                assert (cache.free_blocks(), cache.stats()) == before
            n += 1
        elif op == "release" and tables:
            seq = str(rng.choice(sorted(tables)))
            held = tables.pop(seq)
            freed = cache.free(seq)
            assert freed == sum(
                1 for p in set(held) if not extra.get(p) and not any(
                    p in t for t in tables.values()))
        elif op == "prefix" and tables:
            donor = tables[str(rng.choice(sorted(tables)))]
            shared = donor[:int(rng.integers(0, len(donor) + 1))]
            tokens = bs * (len(shared) + int(rng.integers(1, 9)))
            fresh = cache.blocks_for(tokens) - len(shared)
            if cache.free_blocks() >= fresh:
                t = cache.allocate_with_prefix(f"s{n}", tokens, shared)
                assert t[:len(shared)] == shared
                tables[f"s{n}"] = t
            else:
                with pytest.raises(OutOfKVBlocksError):
                    cache.allocate_with_prefix(f"s{n}", tokens, shared)
            n += 1
        elif op == "cow" and tables and not windowed:
            seq = str(rng.choice(sorted(tables)))
            i = int(rng.integers(0, len(tables[seq])))
            shared = cache.ref_count(tables[seq][i]) > 1
            if shared and not cache.free_blocks():
                with pytest.raises(OutOfKVBlocksError):
                    cache.copy_on_write(seq, i)
                continue
            old, new = cache.copy_on_write(seq, i)
            assert old == tables[seq][i] and (new != old) == shared
            tables[seq][i] = new
            assert cache.block_table(seq) == tables[seq]
        elif op == "incref" and tables and not windowed:
            t = tables[str(rng.choice(sorted(tables)))]
            page = int(rng.choice(t))
            cache.incref([page])
            extra[page] = extra.get(page, 0) + 1
        elif op == "decref" and extra:
            page = int(rng.choice(sorted(extra)))
            last = extra[page] == 1 and not any(
                page in t for t in tables.values())
            assert cache.decref([page]) == int(last)
            extra[page] -= 1
            if not extra[page]:
                del extra[page]
        _check_allocator(cache, tables, extra)
        assert cache.stats()["kv_run_pages_share"] == sum(
            run_pages(t) for t in tables.values()) / max(1, sum(
                len(t) for t in tables.values()))
    for seq in list(tables):
        cache.free(seq)
    cache.decref([p for p, k in extra.items() for _ in range(k)])
    assert cache.free_blocks() == 96 and cache._free._starts == [1]
    for w in cache.windows:
        assert cache._rings[w].free._starts == [1]
    assert cache.stats()["kv_sequences"] == 0 \
        and cache.stats()["kv_run_pages_share"] == 0


def _closed_loop_churn(cache, pool, running, admissions, seed):
    """A closed loop's allocator: ``running`` sequences of the traffic
    file's multiset (each cycle in an order the seed picks), each leaving
    after about its output's steps, the next admitted at once."""
    import heapq

    from benchmark.harness import loadgen
    order = loadgen.ordered(pool, seed, 3)
    rng = np.random.default_rng(seed)
    heap, now, n = [], 0.0, 0
    while n < admissions:
        while len(heap) < running and n < admissions:
            prompt, out = next(order)
            assert cache.can_allocate(prompt + out)
            cache.allocate(f"s{n}", prompt + out)
            heapq.heappush(heap, (now + out + prompt / 80.0
                                  + rng.uniform(0, 3), f"s{n}"))
            n += 1
        now, seq = heapq.heappop(heap)
        cache.free(seq)


@pytest.mark.parametrize("cell,traffic,floor", [
    ("smallthinker_21b_a3b", "serve_closed96_mix8k", 0.85),
    ("laguna_xs_2", "serve_closed64_ctx8k", 0.85),
    # (10 to 58 pages a sequence: the last group of a table is rarely
    # whole, and the multiset itself allows 87.6%)
    ("gpt2_large", "serve_closed32", 0.80),
    # the latent pools (``latent_attention_decode`` takes the same groups
    # since PR 56): tables of 304 to 576 pages, whose multiset allows
    # 99.1%, and of 112 to 192, which allow 97.6% and 97.5%
    ("kimi_k2_7_code", "serve_closed32_ctx8k", 0.97),
    ("longcat_flash_omni", "serve_closed64_ctx2k", 0.95),
    ("kimi_linear_48b_a3b", "serve_closed64", 0.95)])
def test_tables_stay_runs_under_a_cells_churn(cell, traffic, floor):
    """After 5,000 admissions at a serving cell's pools, sequences and
    multiset of lengths, the running tables' pages still lie in whole
    runs of 8: 85% of them and more in every page group (98% at the long
    contexts), and within three points of what the multiset's own page
    counts allow."""
    import json
    import os

    from benchmark.harness import loadgen
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", cell + ".json")) as f:
        config = json.load(f)
    engine = config["serve"]["engine"]
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        pool = loadgen.length_pool(json.load(f))
    windows = {"smallthinker_21b_a3b": (4096,), "laguna_xs_2": (512,)}.get(
        cell, ())
    bs = engine["block_size"]
    cache = PagedKVCache(engine["num_blocks"], bs, windows=windows,
                         max_sequences=engine["max_running"],
                         window_blocks=engine.get("window_blocks"))
    _closed_loop_churn(cache, pool, engine["max_running"], 5000, 44)
    stats = cache.stats()
    assert stats["kv_sequences"] >= engine["max_running"] - 1
    needs = [cache.blocks_for(p + o) for p, o in pool]
    assert stats["kv_run_pages_share"] >= max(
        floor, sum(n // 8 * 8 for n in needs) / sum(needs) - 0.03)
    for w in windows:
        share = stats["kv_window_groups"][w]["run_pages_share"]
        ring = cache.ring_blocks(w)
        held = [min(ring, n) for n in needs]
        assert share >= sum(n // 8 * 8 for n in held) / sum(held) - 0.03
        assert share >= floor or ring < 64


# --------------------------------------------------- incremental decode


def test_gpt2_incremental_decode_matches_full_forward():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    cfg = gpt2.GPT2Config.tiny()
    m = gpt2.GPT2(cfg)
    ids = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 10)))
    params = m.init(jax.random.PRNGKey(0), ids)
    full = m.apply(params, ids)

    cache = gpt2.init_kv_cache(cfg, 2, 32)
    L = jnp.zeros((2,), jnp.int32)
    lg, cache = m.apply(params, ids[:, :6], kv_cache=cache,
                        seq_lengths=L)
    outs, L = [lg], L + 6
    for t in range(6, 10):
        lg, cache = m.apply(params, ids[:, t:t + 1], kv_cache=cache,
                            seq_lengths=L)
        outs.append(lg)
        L = L + 1
    inc = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


def test_llama_incremental_decode_matches_full_forward():
    """GQA + rotary offsets: the decode path must rotate each new
    token by its TRUE absolute position."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    cfg = llama.LlamaConfig.tiny()     # n_kv_heads < n_heads
    m = llama.LlamaModel(cfg)
    ids = jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (2, 9)))
    params = m.init(jax.random.PRNGKey(0), ids)
    full = m.apply(params, ids)

    cache = llama.init_kv_cache(cfg, 2, 32)
    L = jnp.zeros((2,), jnp.int32)
    lg, cache = m.apply(params, ids[:, :5], kv_cache=cache,
                        seq_lengths=L)
    outs, L = [lg], L + 5
    for t in range(5, 9):
        lg, cache = m.apply(params, ids[:, t:t + 1], kv_cache=cache,
                            seq_lengths=L)
        outs.append(lg)
        L = L + 1
    inc = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
