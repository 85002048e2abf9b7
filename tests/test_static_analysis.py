"""rtpulint: the static-analysis gate and its checker fixture matrix.

Three layers:

1. **fixture matrix** — every checker has at least one true-positive
   and one false-positive fixture, plus pragma suppression;
2. **registry round-trips** — the chaos-site and env-var registries
   are checked against the *live tree* in both directions (every use
   declared, every declaration used/exercised), and the generated docs
   must be byte-fresh;
3. **the gate** — `ray_tpu/` must analyze clean modulo the reviewed
   baseline (no unsuppressed findings, no stale baseline entries).
   This is the tier-1 enforcement point: a PR that introduces a
   blocking call in an async def (etc.) fails here.
"""

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu.analysis import analyze_paths, analyze_source
from ray_tpu.analysis import baseline as bl
from ray_tpu.analysis.core import analyze_file, registry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO_ROOT, "ray_tpu")

ALL_CODES = {"RTPU001", "RTPU002", "RTPU003", "RTPU004", "RTPU005",
             "RTPU006", "RTPU007"}


def check(src, select=None, config=None, relpath=None, pragmas=True):
    return analyze_source(textwrap.dedent(src), relpath=relpath,
                          config=config, select=select,
                          respect_pragmas=pragmas)


def codes(findings):
    return sorted(f.code for f in findings)


def test_registry_has_all_checkers():
    assert set(registry()) == ALL_CODES


# --------------------------------------------------------------- RTPU001


def test_blocking_in_async_def_flagged():
    fs = check("""
        import time
        async def f():
            time.sleep(1)
    """, select=["RTPU001"])
    assert codes(fs) == ["RTPU001"]
    assert "time.sleep" in fs[0].message


def test_blocking_in_sync_def_ok():
    assert check("""
        import time
        def f():
            time.sleep(1)
    """, select=["RTPU001"]) == []


def test_await_sleep_ok():
    assert check("""
        import asyncio
        async def f():
            await asyncio.sleep(1)
    """, select=["RTPU001"]) == []


def test_nested_sync_def_inside_async_ok():
    # the nested def runs wherever it's called (thread pool, executor),
    # not on the event loop of the enclosing coroutine
    assert check("""
        import time
        async def f(loop):
            def worker():
                time.sleep(1)
            await loop.run_in_executor(None, worker)
    """, select=["RTPU001"]) == []


def test_blocking_pragma_suppression():
    src = """
        import time
        async def f():
            time.sleep(0)  # rtpulint: ignore[RTPU001]
    """
    assert check(src, select=["RTPU001"]) == []
    assert codes(check(src, select=["RTPU001"], pragmas=False)) == \
        ["RTPU001"]


def test_config_extends_blocking_calls():
    fs = check("""
        async def f():
            heavy_io()
    """, select=["RTPU001"], config={"blocking_calls": ["heavy_io"]})
    assert codes(fs) == ["RTPU001"]


# --------------------------------------------------------------- RTPU002


def test_lock_across_await_flagged():
    fs = check("""
        async def f(self):
            with self._lock:
                await self.flush()
    """, select=["RTPU002"])
    assert codes(fs) == ["RTPU002"]


def test_lock_without_await_ok():
    assert check("""
        async def f(self):
            with self._lock:
                self.n += 1
    """, select=["RTPU002"]) == []


def test_async_lock_across_await_ok():
    assert check("""
        async def f(self):
            async with self._lock:
                await self.flush()
    """, select=["RTPU002"]) == []


# --------------------------------------------------------------- RTPU003


def test_daemon_thread_without_stop_flagged():
    fs = check("""
        import threading
        class Flusher:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
    """, select=["RTPU003"])
    assert codes(fs) == ["RTPU003"]
    assert "daemon thread" in fs[0].message


def test_daemon_thread_with_stop_ok():
    assert check("""
        import threading
        class Flusher:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
            def stop(self):
                self._stop = True
                self._t.join()
    """, select=["RTPU003"]) == []


def test_incref_without_decref_flagged():
    fs = check("""
        class Pages:
            def grab(self, pool, pid):
                pool.incref(pid)
    """, select=["RTPU003"])
    assert codes(fs) == ["RTPU003"]
    assert "decref" in fs[0].message


def test_incref_decref_paired_ok():
    assert check("""
        class Pages:
            def grab(self, pool, pid):
                pool.incref(pid)
            def drop(self, pool, pid):
                pool.decref(pid)
    """, select=["RTPU003"]) == []


def test_error_path_decref_leak_flagged():
    fs = check("""
        def ship(pool, pid, conn):
            pool.incref(pid)
            conn.send(pid)
            pool.decref(pid)
    """, select=["RTPU003"])
    assert codes(fs) == ["RTPU003"]
    assert "straight-line" in fs[0].message


def test_error_path_decref_in_finally_ok():
    assert check("""
        def ship(pool, pid, conn):
            pool.incref(pid)
            try:
                conn.send(pid)
            finally:
                pool.decref(pid)
    """, select=["RTPU003"]) == []


# --------------------------------------------------------------- RTPU004

_SITES_CFG = {"chaos_sites": ["raylet.dispatch", "protocol.send"]}


def test_undeclared_chaos_site_flagged_with_near_miss():
    fs = check("""
        from ray_tpu._private import chaos
        def f():
            chaos.hit("raylet.dispach", None)
    """, select=["RTPU004"], config=_SITES_CFG)
    assert codes(fs) == ["RTPU004"]
    assert "raylet.dispatch" in fs[0].message  # did-you-mean hint


def test_declared_chaos_site_ok():
    assert check("""
        from ray_tpu._private import chaos
        def f():
            chaos.hit("raylet.dispatch", None)
    """, select=["RTPU004"], config=_SITES_CFG) == []


def test_chaos_site_module_constant_resolved():
    assert check("""
        from ray_tpu._private import chaos
        CHAOS_SITE = "protocol.send"
        def f():
            chaos.hit(CHAOS_SITE, None)
    """, select=["RTPU004"], config=_SITES_CFG) == []


def test_chaos_site_unresolvable_flagged():
    fs = check("""
        from ray_tpu._private import chaos
        def f(site):
            chaos.hit(site, None)
    """, select=["RTPU004"], config=_SITES_CFG)
    assert codes(fs) == ["RTPU004"]
    assert "statically" in fs[0].message


# --------------------------------------------------------------- RTPU005

_ENV_CFG = {"env_registry": ["RTPU_TRACE_SAMPLE", "RTPU_CHAOS"]}


def test_unregistered_env_read_flagged():
    fs = check("""
        import os
        v = os.environ.get("RTPU_BRAND_NEW_KNOB")
    """, select=["RTPU005"], config=_ENV_CFG)
    assert codes(fs) == ["RTPU005"]


def test_env_typo_near_miss_message():
    fs = check("""
        import os
        v = os.environ.get("RTPU_TRACE_SAMPEL")
    """, select=["RTPU005"], config=_ENV_CFG)
    assert codes(fs) == ["RTPU005"]
    assert "RTPU_TRACE_SAMPLE" in fs[0].message
    assert "typo" in fs[0].message


def test_registered_env_reads_ok_all_idioms():
    assert check("""
        import os
        a = os.environ.get("RTPU_CHAOS")
        b = os.getenv("RTPU_TRACE_SAMPLE")
        c = os.environ["RTPU_CHAOS"]
        d = "RTPU_CHAOS" in os.environ
        e = os.environ.setdefault("RTPU_TRACE_SAMPLE", "1.0")
    """, select=["RTPU005"], config=_ENV_CFG) == []


def test_non_rtpu_env_reads_ignored():
    assert check("""
        import os
        v = os.environ.get("HOME")
    """, select=["RTPU005"], config=_ENV_CFG) == []


# --------------------------------------------------------------- RTPU006

_FV_CFG = {"field_versions": {("dag_exec", "tc"): (1, 6),
                              ("worker_register", "direct_address"): (1, 7),
                              ("release_lease", "inflight"): (1, 2)}}


def test_unguarded_hard_read_flagged():
    fs = check("""
        def handle(payload):
            return payload["tc"]
    """, select=["RTPU006"], config=_FV_CFG)
    assert codes(fs) == ["RTPU006"]
    assert "1.6" in fs[0].message


def test_get_read_is_absence_tolerant():
    # the dag/channel.py receive-side idiom: .get() + truthiness
    assert check("""
        def handle(payload):
            tc = payload.get("tc")
            if tc:
                attach(tc)
    """, select=["RTPU006"], config=_FV_CFG) == []


def test_tuple_compare_guard_recognized():
    # the schema-1.2 lease idiom: explicit negotiated-version compare
    assert check("""
        def handle(self, payload, conn):
            ver = conn.meta.get("peer_protocol_version") or (1, 0)
            if tuple(ver[:2]) >= (1, 2):
                return payload["inflight"]
            return 0
    """, select=["RTPU006"], config=_FV_CFG) == []


def test_negotiated_flag_guard_recognized():
    # the compiled_dag._negotiate 1.6 idiom: a feature flag computed
    # from the min peer version gates the hard read
    assert check("""
        def recv(self, payload):
            if self._trace_peers:
                span(payload["tc"])
    """, select=["RTPU006"], config=_FV_CFG) == []


def test_min_peer_guard_recognized():
    # the 1.7 direct-lane idiom
    assert check("""
        def register(self, payload, min_peer):
            if min_peer >= (1, 7):
                return payload["direct_address"]
    """, select=["RTPU006"], config=_FV_CFG) == []


def test_field_write_not_flagged():
    # producing the field is fine — we only speak what WE negotiated
    assert check("""
        def build(payload, ctx):
            payload["tc"] = ctx
    """, select=["RTPU006"], config=_FV_CFG) == []


def test_ungated_field_read_ok():
    assert check("""
        def handle(payload):
            return payload["method"]
    """, select=["RTPU006"], config=_FV_CFG) == []


def test_live_tree_version_gate_idioms_pass():
    """dag/channel.py and _private/direct.py read 1.5/1.6/1.7 fields
    behind this codebase's real guard idioms — the checker must
    recognize all of them (zero findings, no pragmas needed)."""
    for rel in ("dag/channel.py", "_private/direct.py"):
        path = os.path.join(PKG, rel)
        fs = analyze_file(path, root=PKG, select=["RTPU006"])
        assert fs == [], f"{rel}: {[f.render() for f in fs]}"


# --------------------------------------------------------------- RTPU007


def test_inert_swallow_in_control_loop_flagged():
    fs = check("""
        def tick(self):
            while True:
                try:
                    self.step()
                except Exception:
                    pass
    """, select=["RTPU007"], relpath="serve/controller.py")
    assert codes(fs) == ["RTPU007"]


def test_swallow_that_logs_ok():
    assert check("""
        def tick(self):
            while True:
                try:
                    self.step()
                except Exception:
                    logger.exception("tick failed")
    """, select=["RTPU007"], relpath="serve/controller.py") == []


def test_swallow_that_records_ok():
    # stashing the error IS a keep-going policy, not silence
    assert check("""
        def tick(self):
            while True:
                try:
                    self.step()
                except Exception as e:
                    self._last_error = e
    """, select=["RTPU007"], relpath="serve/controller.py") == []


def test_swallow_outside_loop_ok():
    assert check("""
        def once(self):
            try:
                self.step()
            except Exception:
                pass
    """, select=["RTPU007"], relpath="serve/controller.py") == []


def test_swallow_outside_control_plane_ok():
    assert check("""
        def tick(self):
            while True:
                try:
                    self.step()
                except Exception:
                    pass
    """, select=["RTPU007"], relpath="util/helpers.py") == []


def test_swallow_pragma_on_except_line():
    assert check("""
        def tick(self):
            while True:
                try:
                    self.step()
                except Exception:  # rtpulint: ignore[RTPU007]
                    pass
    """, select=["RTPU007"], relpath="serve/controller.py") == []


# ------------------------------------------------------------- pragmas


def test_bare_pragma_suppresses_all_codes():
    assert check("""
        import time
        async def f():
            time.sleep(1)  # rtpulint: ignore
    """) == []


def test_own_line_pragma_covers_next_line():
    assert check("""
        import time
        async def f():
            # rtpulint: ignore[RTPU001]
            time.sleep(1)
    """, select=["RTPU001"]) == []


def test_pragma_wrong_code_does_not_suppress():
    fs = check("""
        import time
        async def f():
            time.sleep(1)  # rtpulint: ignore[RTPU002]
    """, select=["RTPU001"])
    assert codes(fs) == ["RTPU001"]


# ------------------------------------------------------------- baseline


def _one_finding(src="""
    import time
    async def f():
        time.sleep(1)
"""):
    fs = check(src, select=["RTPU001"], relpath="pkg/mod.py")
    assert len(fs) == 1
    return fs[0]


def test_baseline_round_trip(tmp_path):
    f = _one_finding()
    p = tmp_path / "bl"
    bl.save(str(p), [f])
    entries = bl.load(str(p))  # --write-baseline emits a TODO comment
    assert len(entries) == 1
    assert entries[0].code == "RTPU001"
    assert entries[0].fingerprint == f.fingerprint()
    un, based, stale = bl.apply([f], entries)
    assert un == [] and based == [f] and stale == []


def test_baseline_requires_justification(tmp_path):
    f = _one_finding()
    p = tmp_path / "bl"
    p.write_text(f"{f.code} {f.relpath} {f.scope} {f.fingerprint()}\n")
    with pytest.raises(ValueError, match="justification"):
        bl.load(str(p))


def test_baseline_rejects_malformed_line(tmp_path):
    p = tmp_path / "bl"
    p.write_text("what even is this\n")
    with pytest.raises(ValueError, match="malformed"):
        bl.load(str(p))


def test_baseline_stale_entry_surfaces(tmp_path):
    f = _one_finding()
    p = tmp_path / "bl"
    p.write_text(f"RTPU001 {f.relpath} {f.scope} {'0' * 12}"
                 f"  # fixed long ago\n")
    un, based, stale = bl.apply([f], bl.load(str(p)))
    assert un == [f] and based == []
    assert len(stale) == 1  # must be deleted: baselines only shrink


def test_fingerprint_stable_across_line_moves():
    a = _one_finding()
    b = _one_finding("""


    import time
    async def f():
        time.sleep(1)
""")
    assert a.line != b.line
    assert a.fingerprint() == b.fingerprint()


def test_fingerprint_changes_with_code_change():
    a = _one_finding()
    b = check("""
        import subprocess
        async def f():
            subprocess.run(["x"])
    """, select=["RTPU001"], relpath="pkg/mod.py")[0]
    assert a.fingerprint() != b.fingerprint()


# ----------------------------------------------------- registry round-trips


def _hit_sites_in_tree():
    """Every chaos.hit site literal in ray_tpu/ (the checker's view)."""
    from ray_tpu.analysis.core import (call_name, const_str,
                                       iter_py_files, module_constants)
    sites = {}
    for fp in iter_py_files([PKG]):
        with open(fp, encoding="utf-8", errors="replace") as fh:
            try:
                tree = ast.parse(fh.read())
            except SyntaxError:
                continue
        if fp.replace(os.sep, "/").endswith("_private/chaos.py"):
            continue
        consts = module_constants(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            name = call_name(node)
            if name is None or not (name.rsplit(".", 1)[-1] == "hit"
                                    or name == "chaos_hit"):
                continue
            site = const_str(node.args[0])
            if site is None and isinstance(node.args[0], ast.Name):
                site = consts.get(node.args[0].id)
            if site:
                sites.setdefault(site, []).append(fp)
    return sites


def test_chaos_registry_round_trip():
    """Both directions against the live tree: every hit site declared
    (RTPU004's job), and every declared site actually hit somewhere —
    a registry row nothing fires is a fault path nothing exercises."""
    from ray_tpu._private.chaos import SITES
    used = _hit_sites_in_tree()
    assert set(used) <= set(SITES), \
        f"undeclared sites in tree: {set(used) - set(SITES)}"
    assert set(SITES) <= set(used), \
        f"declared but never hit: {set(SITES) - set(used)}"


def test_every_chaos_site_exercised_by_tests():
    from ray_tpu._private.chaos import SITES
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    corpus = ""
    for fn in os.listdir(tests_dir):
        if fn.endswith(".py"):
            with open(os.path.join(tests_dir, fn),
                      encoding="utf-8", errors="replace") as fh:
                corpus += fh.read()
    unexercised = [s for s in SITES if s not in corpus]
    assert unexercised == [], \
        f"chaos sites no test injects into: {unexercised}"


def test_env_registry_round_trip():
    """Every RTPU_* read in the tree is registered (the RTPU005 gate,
    asserted directly), and every *static* registry entry corresponds
    to a name the tree actually mentions — entries for removed knobs
    must be deleted, not accumulate."""
    from ray_tpu.analysis.config_registry import (CONFIG_VARS,
                                                  STATIC_VARS)
    from ray_tpu.analysis.docs_gen import scan_env_reads
    scan_paths = [PKG, os.path.dirname(os.path.abspath(__file__))]
    reads = scan_env_reads(scan_paths, REPO_ROOT)
    unregistered = sorted(n for n in reads if n not in CONFIG_VARS)
    assert unregistered == [], \
        f"env reads missing from config_registry: {unregistered}"

    corpus = ""
    for fp in _all_py(scan_paths):
        with open(fp, encoding="utf-8", errors="replace") as fh:
            corpus += fh.read()
    dead = sorted(n for n in STATIC_VARS if n not in corpus)
    assert dead == [], f"registry entries nothing mentions: {dead}"


def _all_py(paths):
    from ray_tpu.analysis.core import iter_py_files
    return iter_py_files(paths)


def test_generated_docs_are_fresh():
    """docs/CONFIGURATION.md and the chaos table in
    docs/FAULT_TOLERANCE.md must match a regeneration byte-for-byte —
    run `python -m ray_tpu.analysis --gen-docs` after touching the
    registries."""
    from ray_tpu.analysis.docs_gen import generate_all
    stale = [os.path.relpath(p, REPO_ROOT)
             for p, (_c, changed) in
             generate_all(REPO_ROOT, write=False).items() if changed]
    assert stale == [], f"stale generated docs: {stale}"


# ------------------------------------------------------------- the gate


def test_ray_tpu_tree_lints_clean():
    """THE gate: zero unsuppressed findings over ray_tpu/, no stale
    baseline entries. New findings either get fixed, carry an inline
    `# rtpulint: ignore[...]` pragma with a reason, or (reviewed) join
    .rtpulint-baseline with a justification."""
    from ray_tpu.analysis.cli import DEFAULT_EXCLUDES
    findings = analyze_paths([PKG], root=PKG, exclude=DEFAULT_EXCLUDES)
    entries = bl.load(os.path.join(REPO_ROOT, bl.DEFAULT_BASENAME))
    assert len(entries) < 15, "baseline must stay small — fix, don't park"
    unsuppressed, _based, stale = bl.apply(findings, entries)
    assert unsuppressed == [], "\n".join(f.render() for f in unsuppressed)
    assert stale == [], \
        f"stale baseline entries (delete them): {[e.key() for e in stale]}"


def test_cli_json_smoke():
    """`ray-tpu lint --json` end to end in a subprocess (the scripts/cli
    delegation path), machine-readable output contract."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu.scripts.cli import main; "
         "main(['lint', '--json', 'ray_tpu'])"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**os.environ, "PYTHONPATH": REPO_ROOT}, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == 1
    assert doc["findings"] == []
    assert doc["stale_baseline"] == []
    assert set(doc["checkers"]) == ALL_CODES


def test_syntax_error_reported_as_rtpu000(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text("def f(:\n")
    fs = analyze_file(str(p), root=str(tmp_path))
    assert codes(fs) == ["RTPU000"]
