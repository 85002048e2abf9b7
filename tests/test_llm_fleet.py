"""Fleet-efficient LLM serving (docs/LLM_SERVING.md): radix prefix KV
cache (refcounted page sharing, copy-on-extend, LRU eviction),
prefill/decode disaggregation (KV handoff between engines, the
``llm.kv_ship`` chaos site's fallback-to-re-prefill), and greedy
speculative decoding (token-for-token identical to sequential greedy
for the toy model, gpt2, and llama-with-a-gpt2-draft), plus the
role-aware router/autoscaler units and the llm-chat-disagg game day
with exact per-token + cache-hit reconciliation. Tier-1, CPU-only.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from ray_tpu.serve.llm import (EngineConfig, KVShipper, LLMEngine,
                               PagedKVCache, RadixPrefixCache,
                               SamplingParams, ToyAdapter, greedy_verify)
from ray_tpu.serve.llm.kv_cache import OutOfKVBlocksError
from ray_tpu.serve.llm.model_runner import make_adapter
from ray_tpu.serve.llm.spec_decode import ToyDraft, make_draft

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _drain(eng, sid, timeout=60.0):
    toks, cur = [], 0
    deadline = time.time() + timeout
    while time.time() < deadline:
        ch = eng.poll(sid, cur, max_wait_s=5.0)
        toks += ch["tokens"]
        cur = ch["cursor"]
        if ch["done"]:
            return toks, ch
    raise TimeoutError(f"stream {sid} never finished")


# -------------------------------------------- refcounted page allocator


def test_kv_refcount_share_cow_eviction_interleave():
    """Satellite: refcounted page lifetimes survive an interleaving of
    prefix sharing, copy-on-write privatization, sequence completion
    and cache-branch release — pages return to the free list exactly
    when their LAST reference drops, and never twice."""
    c = PagedKVCache(num_blocks=9, block_size=4)     # 8 usable pages
    a = c.allocate("a", 16)                          # 4 pages, ref 1
    assert c.free_blocks() == 4
    shared = a[:2]
    # b maps a's first two pages read-only + 2 fresh
    b = c.allocate_with_prefix("b", 16, shared)
    assert b[:2] == shared and c.free_blocks() == 2
    assert all(c.ref_count(p) == 2 for p in shared)

    # the "prefix cache" takes its own reference on one shared page
    c.incref([shared[0]])
    assert c.ref_count(shared[0]) == 3

    # b writes into a shared page -> private copy, a's view unchanged
    old, new = c.copy_on_write("b", 1)
    assert old == shared[1] and new != old
    assert c.block_table("b")[1] == new
    assert c.block_table("a")[1] == old
    assert c.ref_count(old) == 1 and c.ref_count(new) == 1
    assert c.free_blocks() == 1

    # a already-private page is a no-op COW
    o2, n2 = c.copy_on_write("b", 1)
    assert (o2, n2) == (new, new)

    # a finishes: its private pages free; shared[0] lives on (b + cache)
    freed = c.free("a")
    assert freed == 3                                # a[1..3]; a[0] shared
    assert c.ref_count(shared[0]) == 2
    # b finishes: everything b held frees, shared[0] still cached
    c.free("b")
    assert c.ref_count(shared[0]) == 1
    assert c.free_blocks() == 7
    # the cache drops its branch: the final reference frees the page
    assert c.decref([shared[0]]) == 1
    assert c.free_blocks() == 8
    # double release is inert, not a corruption
    assert c.decref([shared[0]]) == 0
    assert c.free_blocks() == 8

    # dead pages are not shareable
    with pytest.raises(ValueError):
        c.incref([shared[0]])
    with pytest.raises(ValueError):
        c.allocate_with_prefix("c", 8, [shared[0]])


def test_kv_cow_exhaustion_and_exact_admission():
    c = PagedKVCache(num_blocks=4, block_size=4)     # 3 usable
    c.allocate("a", 8)                               # 2 pages
    c.allocate_with_prefix("b", 12, c.block_table("a"))  # +1 fresh
    assert c.free_blocks() == 0
    with pytest.raises(OutOfKVBlocksError):
        c.copy_on_write("b", 0)                      # shared, no free page
    with pytest.raises(OutOfKVBlocksError):
        c.allocate("c", 4)


# ------------------------------------------------------- radix prefix


def test_radix_prefix_lookup_insert_evict():
    """Tree semantics: whole-page matches only, first-writer-wins
    publication, LRU eviction skips pages live sequences still map."""
    c = PagedKVCache(num_blocks=12, block_size=4)
    pc = RadixPrefixCache(c)
    prompt = list(range(4 * 3 + 2))                  # 3 full pages + 2
    t = c.allocate("donor", len(prompt))
    assert pc.insert(prompt, t) == 3                 # partial page unshared
    assert len(pc) == 3
    # donor finishes; cache refs keep all 3 published pages resident
    c.free("donor")
    assert all(c.ref_count(p) == 1 for p in t[:3])

    # lookup: full-page prefix only, longest match wins
    m, pages = pc.lookup(prompt)
    assert m == 12 and pages == t[:3]
    m, pages = pc.lookup(prompt[:7])                 # 1 full page + 3
    assert m == 4 and pages == t[:1]
    m, pages = pc.lookup([999] * 8)
    assert m == 0 and pages == []

    # a consumer maps the prefix; eviction must not touch its pages
    c.allocate_with_prefix("user", len(prompt), t[:3])
    # burn the remaining pool so eviction has something to do
    filler = c.allocate("filler", 4 * c.free_blocks())
    assert c.free_blocks() == 0
    freed = pc.evict(1)
    assert freed == 0                                # everything referenced
    c.free("filler")
    c.free("user")
    # now the leaf branch (deepest first) is evictable, LRU order
    freed = pc.evict(c.free_blocks() + 2)
    assert freed >= 2
    st = pc.stats()
    assert st["prefix_evicted_pages"] == freed
    assert st["prefix_hit_tokens_total"] == 16

    # insert against freed pages must not publish dangling entries
    assert pc.insert(prompt, filler[:3]) == 0
    m2, pages2 = pc.lookup(prompt)
    for p in pages2:
        assert c.ref_count(p) >= 1


def _toy_engine(**cfg):
    defaults = dict(num_blocks=64, block_size=8, max_seq_len=256,
                    max_running=8)
    defaults.update(cfg)
    return LLMEngine(ToyAdapter(), EngineConfig(**defaults))


def _gen(eng, prompts, ntok=10, rid_prefix="r"):
    out = []
    for i, p in enumerate(prompts):
        sid = eng.add_request(
            list(p), SamplingParams(max_new_tokens=ntok),
            request_id=f"{rid_prefix}{i}")
        toks, ch = _drain(eng, sid)
        assert not ch.get("error"), ch
        out.append(toks)
    return out


def test_prefix_cache_engine_identity_and_hit_accounting():
    """Warm (prefix-cached) generation is token-identical to cold, the
    engine's cache-hit counter matches the tree's, and hits show up in
    the per-request ledger column reconcile C11 audits."""
    rng = np.random.RandomState(3)
    sys_prompt = [int(t) for t in rng.randint(0, 256, 24)]  # 3 pages
    prompts = [sys_prompt + [int(t) for t in rng.randint(0, 256, n)]
               for n in (5, 9, 13, 2, 17, 8)]

    cold = _gen(_toy_engine(), prompts)
    eng = _toy_engine(enable_prefix_cache=True)
    warm = _gen(eng, prompts)
    assert warm == cold

    m = eng.metrics()
    assert m["cache_hit_tokens_total"] > 0
    assert m["cache_hit_tokens_total"] == \
        eng.prefix_cache.stats()["prefix_hit_tokens_total"]
    # ledger rows carry (rid, n, reason, n_prompt, cached): the sum of
    # the cached column IS the counter (C11's replica-level join)
    ledger = eng.token_ledger()
    assert sum(r[4] for r in ledger) == m["cache_hit_tokens_total"]
    for i, r in enumerate(sorted(ledger, key=lambda r: r[0])):
        assert r[3] == len(prompts[i])
    # every request after the first shares >= 2 full pages (the third
    # page is sacrificed to copy-on-extend when the tail is partial)
    by_rid = {r[0]: r[4] for r in ledger}
    assert all(by_rid[f"r{i}"] >= 16 for i in range(1, len(prompts)))
    eng.stop()


def test_prefix_cache_copy_on_extend_does_not_corrupt_shared_pages():
    """A warm request whose cached prefix ends mid-page privatizes that
    page before writing (copy-on-extend); the shared original must
    still serve later requests byte-identically."""
    rng = np.random.RandomState(7)
    base = [int(t) for t in rng.randint(0, 256, 20)]  # 2.5 pages @ bs 8
    divergent = base + [int(t) for t in rng.randint(0, 256, 11)]
    eng = _toy_engine(enable_prefix_cache=True)
    cold_eng = _toy_engine()
    # publish base; extend it (COW on page 2); then replay base EXACTLY
    seq = [base, divergent, base, divergent]
    warm = _gen(eng, seq)
    cold = _gen(cold_eng, seq)
    assert warm == cold
    assert eng.metrics()["cache_hit_tokens_total"] > 0
    eng.stop()
    cold_eng.stop()


def test_prefix_cache_eviction_under_pool_pressure():
    """A pool too small for the working set still admits everything:
    the engine evicts cold branches instead of shedding, and outputs
    stay identical to an uncached engine."""
    rng = np.random.RandomState(11)
    prompts = [[int(t) for t in rng.randint(0, 256, 24 + (i % 3) * 8)]
               for i in range(10)]
    small = dict(num_blocks=24, block_size=8, max_running=2)
    warm_eng = _toy_engine(enable_prefix_cache=True, **small)
    warm = _gen(warm_eng, prompts, ntok=6)
    cold = _gen(_toy_engine(**small), prompts, ntok=6)
    assert warm == cold
    assert warm_eng.prefix_cache.stats()["prefix_evicted_pages"] > 0
    warm_eng.stop()


# --------------------------------------------------- speculative decode


def test_greedy_verify_accept_reject_bonus():
    # full acceptance -> every proposal + the bonus token
    assert greedy_verify([5, 1, 2, 3], [1, 2, 3, 9]) == [1, 2, 3, 9]
    # first mismatch replaced by the target's token, rest discarded
    assert greedy_verify([5, 1, 2, 3], [1, 7, 8, 9]) == [1, 7]
    assert greedy_verify([5, 1, 2, 3], [4, 7, 8, 9]) == [4]
    # window of 1 (no proposals) degenerates to plain greedy
    assert greedy_verify([5], [6]) == [6]


@pytest.mark.parametrize("draft_seed", [0, 7])
def test_spec_decode_toy_identity(draft_seed):
    """Speculative greedy == sequential greedy, token for token — with
    a perfect draft (same seed: ~100% acceptance) AND an adversarial
    one (different seed: constant rejection)."""
    rng = np.random.RandomState(13)
    prompts = [[int(t) for t in rng.randint(0, 256, n)]
               for n in (4, 11, 23, 7)]
    base = _gen(_toy_engine(), prompts, ntok=18)
    eng = _toy_engine(spec_k=3,
                      draft_model_config={"seed": draft_seed})
    spec = _gen(eng, prompts, ntok=18)
    assert spec == base
    m = eng.metrics()
    assert m["spec_draft_tokens_total"] > 0
    assert 0 <= m["spec_accepted_tokens_total"] \
        <= m["spec_draft_tokens_total"]
    if draft_seed == 0:   # draft IS the target's LM -> full acceptance
        assert m["spec_accepted_tokens_total"] == \
            m["spec_draft_tokens_total"]
    eng.stop()


def _flax_identity(target_kind, draft_model, prompts, ntok=6):
    cfgkw = dict(num_blocks=64, block_size=8, max_seq_len=128,
                 max_running=4)
    base_eng = LLMEngine(make_adapter(target_kind),
                         EngineConfig(**cfgkw))
    base = _gen(base_eng, prompts, ntok=ntok, rid_prefix="b")
    base_eng.stop()
    spec_eng = LLMEngine(
        make_adapter(target_kind),
        EngineConfig(spec_k=2, draft_model=draft_model, **cfgkw))
    spec = _gen(spec_eng, prompts, ntok=ntok, rid_prefix="s")
    m = spec_eng.metrics()
    spec_eng.stop()
    assert spec == base, (target_kind, draft_model)
    assert m["spec_draft_tokens_total"] > 0


def test_spec_decode_gpt2_batched_verify_identity():
    """Satellite numerics: gpt2's ONE batched multi-token verify step
    through the paged decode path commits exactly what sequential
    greedy commits, over seeded prompts."""
    rng = np.random.RandomState(17)
    prompts = [[int(t) for t in rng.randint(0, 512, n)]
               for n in (5, 12, 9)]
    _flax_identity("gpt2", "gpt2", prompts)


def test_spec_decode_llama_with_gpt2_draft_identity():
    """Satellite numerics: a gpt2 tiny draft legally drafts for a llama
    tiny target (both 512-token vocabs); verification stays
    token-identical no matter how bad the cross-model proposals are."""
    rng = np.random.RandomState(19)
    prompts = [[int(t) for t in rng.randint(0, 512, n)]
               for n in (6, 13)]
    _flax_identity("llama", "gpt2", prompts)


def test_spec_decode_composes_with_prefix_cache():
    rng = np.random.RandomState(23)
    sys_prompt = [int(t) for t in rng.randint(0, 256, 16)]
    prompts = [sys_prompt + [int(t) for t in rng.randint(0, 256, n)]
               for n in (3, 8, 5)]
    base = _gen(_toy_engine(), prompts, ntok=12)
    eng = _toy_engine(enable_prefix_cache=True, spec_k=3)
    both = _gen(eng, prompts, ntok=12)
    assert both == base
    m = eng.metrics()
    assert m["cache_hit_tokens_total"] > 0
    assert m["spec_draft_tokens_total"] > 0
    eng.stop()


# ------------------------------------------------ disaggregation (engine)


def test_disagg_engine_roundtrip_identity_no_leaked_pages():
    """Satellite: prefill_export -> KVShipper -> adopt_request across
    two engines is output-identical to a unified engine, the ledgers
    split into handoff + completion rows, and both pools drain to zero
    used pages when the streams finish."""
    rng = np.random.RandomState(29)
    prompts = [[int(t) for t in rng.randint(0, 256, n)]
               for n in (21, 9, 33)]
    unified = _gen(_toy_engine(), prompts, ntok=12)

    pe, de = _toy_engine(), _toy_engine()
    shipper = KVShipper("test")          # no plasma -> inline lane
    outs = []
    for i, p in enumerate(prompts):
        sampling = SamplingParams(max_new_tokens=12)
        sid = pe.prefill_export(list(p), sampling,
                                request_id=f"r{i}")
        toks, ch = _drain(pe, sid)
        export = pe.take_export(sid)
        assert export is not None and export["first_token"] == toks[0]
        desc = shipper.ship({"kv": export["kv"]})
        assert desc["lane"] == "inline"
        frame = shipper.receive(desc)
        did = de.adopt_request(list(p), export["first_token"],
                               frame["kv"], sampling,
                               request_id=f"r{i}")
        dtoks, dch = _drain(de, did)
        assert not dch.get("error"), dch
        outs.append(dtoks)
    assert outs == unified

    pl, dl = pe.token_ledger(), de.token_ledger()
    assert all(r[2] == "handoff" and r[1] == 1 for r in pl)
    assert all(r[2] == "length" and r[1] == 12 for r in dl)
    assert [r[3] for r in sorted(pl)] == [len(p) for p in prompts]
    # cached column on the decode side = whole adopted prompt (C11:
    # adopted tokens are cache-hit tokens — no prefill ran for them)
    assert [r[4] for r in sorted(dl)] == [len(p) for p in prompts]
    assert pe.metrics()["kv_blocks_used"] == 0
    assert de.metrics()["kv_blocks_used"] == 0
    pe.stop()
    de.stop()


def test_disagg_corrupt_frame_detected_by_crc():
    """A torn frame never reaches deserialization: flip one byte and
    receive() returns None (re-prefill signal), not garbage."""
    shipper = KVShipper("crc")
    desc = shipper.ship({"kv": {"kind": "toy", "n": 3,
                                "pages": np.ones((2, 8, 4))}})
    desc = dict(desc)
    body = bytearray(desc["b"])
    body[len(body) // 2] ^= 0x5A
    desc["b"] = bytes(body)
    assert shipper.receive(desc) is None


# ----------------------------------------- role-aware router/autoscaler


class _FakeReplica:
    def __init__(self, hex_id):
        self._id_hex = hex_id


def test_replica_set_tracks_roles():
    from ray_tpu.serve._private.router import ReplicaSet
    rs = ReplicaSet("d", 8)
    reps = [_FakeReplica(f"{i:02d}aa") for i in range(3)]
    rs.update_replicas(reps, replica_roles={
        "00aa": "prefill", "01aa": "decode", "02aa": "decode"})
    assert rs.disaggregated()
    assert rs.role_members("prefill") == {"00aa"}
    assert rs.role_members("decode") == {"01aa", "02aa"}
    # a role map referencing dead replicas is filtered on update
    rs.update_replicas(reps[:1], replica_roles={
        "00aa": "prefill", "01aa": "decode"})
    assert not rs.disaggregated()     # no live decode replica
    # no roles at all -> unified
    rs.update_replicas(reps)
    assert not rs.disaggregated() and rs.role_members("prefill") == set()


def test_controller_role_assignment_is_age_stable():
    from ray_tpu.serve.controller import ServeController
    info = type("I", (), {})()
    info.config = {"llm_roles": {"prefill": 1, "decode": 2}}
    info.replica_names = {"b" * 8: "rep#2", "a" * 8: "rep#1",
                          "c" * 8: "rep#3"}
    roles = ServeController._llm_roles_map(
        info, ["c" * 8, "a" * 8, "b" * 8])
    assert roles == {"a" * 8: "prefill", "b" * 8: "decode",
                     "c" * 8: "decode"}
    # oldest replica keeps prefill across membership churn
    roles2 = ServeController._llm_roles_map(info, ["b" * 8, "a" * 8])
    assert roles2 == {"a" * 8: "prefill", "b" * 8: "decode"}
    info.config = {}
    assert ServeController._llm_roles_map(info, ["a" * 8]) is None


def test_autoscaler_per_role_and_cache_hit_signals():
    from ray_tpu.serve._private.autoscaling import (AutoscalingConfig,
                                                    AutoscalingPolicy)
    cfg = AutoscalingConfig(min_replicas=1, max_replicas=10,
                            target_tokens_per_s_per_replica=100.0,
                            upscale_delay_s=0.0, downscale_delay_s=0.0)
    # cache-hit tokens/s count as served demand: 150 generated + 150
    # cache-skipped needs 3 replicas at a 100 tok/s target
    p = AutoscalingPolicy(cfg)
    assert p.get_decision(2, 0.0, now=0.0, signals={
        "tokens_per_s": 150.0,
        "cache_hit_tokens_per_s": 150.0}) == 3
    # per-role: a saturated decode tier can't hide behind an idle
    # prefill tier — ceil(10/100)=1 prefill + ceil(250/100)=3 decode
    p2 = AutoscalingPolicy(cfg)
    assert p2.get_decision(3, 0.0, now=0.0, signals={
        "tokens_per_s": 260.0,
        "per_role": {"prefill": {"tokens_per_s": 10.0},
                     "decode": {"tokens_per_s": 250.0}}}) == 4


# -------------------------------------------- subprocess isolation tests


def _run_script(script, extra_env=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RTPU_PRESTART_WORKERS="0")
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True,
                          timeout=timeout, cwd=REPO_ROOT)


_DISAGG_SERVE_SCRIPT = r"""
import json, random
import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm import LLMServer
from ray_tpu.actor import get_actor_by_id

ray_tpu.init(num_cpus=8, object_store_memory=128*1024*1024,
             _system_config={"prestart_workers": False})
eng = {"num_blocks": 128, "block_size": 8, "max_seq_len": 256}
dep = serve.deployment(name="d", num_replicas=3,
                       llm_roles={"prefill": 1, "decode": 2},
                       max_concurrent_queries=16)(LLMServer)
h = serve.run(dep.bind("toy", {"per_seq_delay_s": 0.001}, eng),
              name="d", route_prefix="/d")
dep_u = serve.deployment(name="u", num_replicas=1,
                         max_concurrent_queries=16)(LLMServer)
hu = serve.run(dep_u.bind("toy", {"per_seq_delay_s": 0.001}, eng),
               name="u", route_prefix="/u")

base = [random.Random("sys").randrange(256) for _ in range(24)]
streams = []
for i in range(6):
    rng = random.Random(i)
    p = base + [rng.randrange(256) for _ in range(rng.randrange(3, 20))]
    payload = {"tokens": p, "max_new_tokens": 12}
    got = [t for ch in h.stream(payload, request_id=f"r{i}")
           for t in ch.get("tokens") or ()]
    want = [t for ch in hu.stream(payload, request_id=f"u{i}")
            for t in ch.get("tokens") or ()]
    streams.append({"rid": f"r{i}", "n": len(got),
                    "identical": got == want})

ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
_, table = ray_tpu.get(ctrl.get_route_table.remote(), timeout=240)
roles = table["d"].get("replica_roles") or {}
per_replica = {}
for hex_id, role in roles.items():
    rep = get_actor_by_id(hex_id)
    m = ray_tpu.get(rep.handle_request.remote("__llm_metrics__", (), {}),
                    timeout=10)
    per_replica[role + ":" + hex_id[:6]] = {
        "kv_used": m.get("kv_blocks_used"),
        "reasons": sorted({r[2] for r in (m.get("token_ledger") or [])}),
    }
print("VERDICT=" + json.dumps({
    "streams": streams,
    "roles": sorted(roles.values()),
    "per_replica": per_replica}))
serve.shutdown(); ray_tpu.shutdown()
"""


def test_disagg_serve_two_hop_end_to_end():
    """The full serve path: llm_roles in the deployment config, roles
    published in the route table, every admission routed
    prefill->decode with a KV handoff, streams identical to a unified
    deployment, zero pages leaked anywhere."""
    r = _run_script(_DISAGG_SERVE_SCRIPT)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("VERDICT=")]
    assert line, r.stdout + r.stderr
    v = json.loads(line[0][len("VERDICT="):])
    assert v["roles"] == ["decode", "decode", "prefill"], v
    assert all(s["identical"] and s["n"] == 12 for s in v["streams"]), v
    reasons = {k: d["reasons"] for k, d in v["per_replica"].items()}
    assert any("handoff" in rs for k, rs in reasons.items()
               if k.startswith("prefill")), reasons
    assert any("length" in rs for k, rs in reasons.items()
               if k.startswith("decode")), reasons
    assert all(d["kv_used"] == 0 for d in v["per_replica"].values()), v


def test_kv_ship_chaos_falls_back_to_reprefill():
    """Satellite: seeded chaos at ``llm.kv_ship`` (drop, corrupt, reset
    — each mid-handoff) degrades every faulted admission to a decode-
    side re-prefill: all streams complete with outputs identical to a
    unified deployment, nothing corrupted, no KV pages leaked."""
    chaos = {"seed": 31, "schedule": [
        {"site": "llm.kv_ship", "op": "drop", "at": 1},
        {"site": "llm.kv_ship", "op": "corrupt", "at": 2},
        {"site": "llm.kv_ship", "op": "reset", "at": 3},
    ]}
    r = _run_script(_DISAGG_SERVE_SCRIPT,
                    {"RTPU_CHAOS": json.dumps(chaos)})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("VERDICT=")]
    assert line, r.stdout + r.stderr
    v = json.loads(line[0][len("VERDICT="):])
    # the first three admissions each ate a distinct mid-handoff fault
    # and still produced full-length, byte-identical output
    assert all(s["identical"] and s["n"] == 12 for s in v["streams"]), v
    # and no replica leaked a page over the fallback path
    assert all(d["kv_used"] == 0 for d in v["per_replica"].values()), v


def test_llm_chat_disagg_gameday_reconciles():
    """Acceptance: the disaggregated llm-chat game day — Zipf shared-
    prefix tenants, two-hop admissions, rolling update mid-run —
    grades fully reconciled: 0 failed streams, exact per-token AND
    cache-hit-token ledger joins (checks C10 + C11)."""
    script = r"""
import json
from ray_tpu.gameday.runner import run_scenario
from ray_tpu.gameday.scenario import load_scenario
res = run_scenario(load_scenario("llm-chat-disagg"), scale=0.4,
                   dashboard_port=18477)
out = {
    "passed": res.passed,
    "failed": res.report["overall"]["failed"],
    "admitted": res.report["overall"]["admitted"],
    "llm": res.report.get("llm"),
    "checks": {c["name"]: c["ok"]
               for c in res.reconciliation.get("checks", [])},
    "details": [c for c in res.reconciliation.get("checks", [])
                if not c["ok"]],
}
print("GAMEDAY=" + json.dumps(out))
"""
    r = _run_script(script, timeout=300)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("GAMEDAY=")]
    assert line, r.stdout + r.stderr
    out = json.loads(line[0][len("GAMEDAY="):])
    assert out["failed"] == 0, out
    assert out["admitted"] > 30, out
    assert out["checks"].get("llm-tokens") is True, out["details"]
    assert out["checks"].get("llm-cache-hit") is True, out["details"]
    assert out["passed"], out["details"]
    assert out["llm"]["tokens_total"] > 100, out["llm"]
