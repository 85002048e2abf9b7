"""The readers of what the program records of its own steps
(``benchmark/harness/program_spans.py`` and the per-layer metrics on
it): on a hand-made ``step_log`` and a hand-made device trace whose
answers are known, and on a small recorded ``.xplane.pb`` (seven engine
steps of a two-layer GPT-2 on the CPU under ``jax.profiler``, cut down to
the program's own annotations, so it holds no device plane)."""

import importlib.util
import os
import shutil

import pytest

from benchmark.harness import cells, program_spans as ps
from benchmark.harness.xplane import Event, Trace, load, window_of

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "cpu_engine_steps.xplane.pb")
MS = 1_000_000


def reader(name):
    path = os.path.join(cells.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, t0, t1, children=(), **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs,
            "children": list(children)}


def runner(op, t0, build, dispatch, fetch):
    a, b, c = t0 + build, t0 + build + dispatch, t0 + build + dispatch + fetch
    return [span("runner.build_inputs", t0, a, op=op, B=16, S=8),
            span("runner.dispatch", a, b, B=16, S=8, first_call=False),
            span("runner.fetch", b, c, bytes=1)]


def step(t0, prefill=False):
    """A decode of 1 + 2 + 95 ms, a commit of 1 ms, 0.5 ms of admission;
    with ``prefill`` a prefill of 1 + 3 + 110 ms and its commit of 1 ms.
    The step's own statements take 0.5 ms more at its end."""
    m = 1e-3
    kids = [span("llm.step.decode", t0, t0 + 98 * m,
                 runner("decode", t0, 1 * m, 2 * m, 95 * m), n=16),
            span("llm.step.commit", t0 + 98 * m, t0 + 99 * m, n=16,
                 finished=0),
            span("llm.step.admit", t0 + 99 * m, t0 + 99.5 * m,
                 admitted=int(prefill), prefill_tokens=0, waiting_left=0)]
    end = t0 + 99.5 * m
    if prefill:
        kids += [span("llm.step.prefill", end, end + 114 * m,
                      runner("prefill", end, 1 * m, 3 * m, 110 * m), n=1,
                      tokens=300),
                 span("llm.step.commit", end + 114 * m, end + 115 * m, n=1,
                      finished=0)]
        end += 115 * m
    return span("llm.step", t0, end + 0.5 * m, kids, i=0, running=16,
                waiting=16)


class Obs:
    """What run.py hands a reader, as far as these readers look."""
    t0, t1 = 100.0, 101.0
    trace = None
    trace_window = None
    cell = {"name": "no.such.cell"}

    def __init__(self, step_log=None, request_log=None, **more):
        self.engine_metrics = {"itl_p50_s": 0.1}
        if step_log is not None:
            self.engine_metrics["step_log"] = step_log
        if request_log is not None:
            self.engine_metrics["request_log"] = request_log
        self.__dict__.update(more)


def log_of_five():
    # one step straddles each edge of [100, 101]; of the three inside,
    # the second carries a prefill
    return [step(99.95), step(100.1), step(100.3, prefill=True),
            step(100.7), step(100.95)]


STEP_LOG_READERS = ["engine_step_wall_p50_ms.serve",
                    "engine_self_p50_ms.serve", "prefill_step_share.serve",
                    "runner_host_p50_ms.serve"]


@pytest.mark.parametrize("name,value", zip(STEP_LOG_READERS, [
    100.0,            # median of 100, 215, 100
    2.0,              # 100 - 98 of the runner (215 - 212 in the other)
    100.0 / 3,        # one step of three
    3.0]))            # build 1 + dispatch 2 of the decode call
def test_step_log_readers(name, value, capsys):
    assert reader(name).read(Obs(log_of_five())) == pytest.approx(value)


@pytest.mark.parametrize("name", STEP_LOG_READERS + [
    "queue_wait_p50_ms.serve", "prefill_device_ms.serve",
    "idle_in_runner_share.serve", "idle_in_engine_share.serve",
    "head_loss_step_share.train", "optimizer_step_share.train",
    "feed_host_batch_p50_ms.train"])
@pytest.mark.parametrize("obs", [
    Obs(), Obs([], []), Obs([step(99.95), step(100.95)], [])],
    ids=["parent-program", "empty-logs", "only-straddling-steps"])
def test_nothing_to_read_is_none_not_zero(name, obs):
    assert reader(name).read(obs) is None


def test_a_step_is_its_children_and_its_self():
    for st in log_of_five():
        kids = sum(ps.ms(c) for c in st["children"])
        assert ps.ms(st) == pytest.approx(kids + 0.5)
        assert ps.self_ms(st) == pytest.approx(
            ps.ms(st) - sum(ps.ms(s) for s in ps.walk(st)
                            if s["name"].startswith("runner.")))


def test_queue_wait_reads_the_requests_that_arrived_in_the_window():
    def req(t_arrival, wait):
        return {"request_id": "r", "t_arrival": t_arrival,
                "t_admit": None if wait is None else t_arrival + wait}
    log = [req(99.0, 5.0), req(100.2, 0.010), req(100.4, 0.030),
           req(100.6, 0.050), req(100.8, None), req(101.5, 9.0)]
    assert reader("queue_wait_p50_ms.serve").read(
        Obs([], log)) == pytest.approx(30.0)


# ------------------------------------------- device trace, hand-made

def annotation_events():
    """Two steps on the profiler's clock: decode [0,100) (build [0,2),
    dispatch [2,5), fetch [5,100)), commit [100,102), admit [102,103);
    the second step [110,330) also holds a prefill [215,325)."""
    ev = []
    for base, prefill in ((0, False), (110, True)):
        ev += [Event("llm.step", base * MS, (220 if prefill else 104) * MS),
               Event("llm.step.decode", base * MS, 100 * MS),
               Event("runner.build_inputs", base * MS, 2 * MS),
               Event("runner.dispatch", (base + 2) * MS, 3 * MS),
               Event("runner.fetch", (base + 5) * MS, 95 * MS),
               Event("llm.step.commit", (base + 100) * MS, 2 * MS),
               Event("llm.step.admit", (base + 102) * MS, 1 * MS)]
    ev += [Event("llm.step.prefill", 215 * MS, 110 * MS),
           Event("runner.build_inputs", 215 * MS, 1 * MS),
           Event("runner.dispatch", 216 * MS, 4 * MS),
           Event("runner.fetch", 220 * MS, 105 * MS)]
    return sorted(ev, key=lambda e: e.start)


def device_obs():
    # busy: [4,98) and [114,208) (decode programs), [219,322) (prefill)
    ops = [Event("%fusion.1 = f32[8]{0} fusion()", 4 * MS, 94 * MS),
           Event("%fusion.1 = f32[8]{0} fusion()", 114 * MS, 94 * MS),
           Event("%fusion.2 = f32[8]{0} fusion()", 219 * MS, 103 * MS)]
    obs = Obs(trace=Trace({"/device:TPU:0": ops}, {}, [], {}),
              trace_window=(0, 340 * MS))
    obs._program_annotations = annotation_events()
    return obs


def test_idle_is_cut_by_the_spans_that_cover_it_and_adds_up(capsys):
    obs = device_obs()
    shares = ps.idle_shares(obs)
    # gaps: [0,4): build [0,2) and dispatch [2,4) -> runner 4;
    # [98,114): fetch to 100, then commit, admission and between steps
    # to 110, then build and dispatch to 114 -> fetch 2, engine 10,
    # runner 4; [208,219): fetch to 210, engine to 215, build and
    # dispatch to 219 -> fetch 2, engine 5, runner 4; [322,340): fetch
    # to 325, engine 15
    assert shares["runner"] == pytest.approx(100 * 12 / 340)
    assert shares["fetch"] == pytest.approx(100 * 7 / 340)
    assert shares["engine"] == pytest.approx(100 * 30 / 340)
    busy = 94 + 94 + 103
    assert sum(shares.values()) == pytest.approx(100 * (340 - busy) / 340)
    assert reader("idle_in_runner_share.serve").read(obs) \
        == pytest.approx(shares["runner"])
    assert reader("idle_in_engine_share.serve").read(obs) \
        == pytest.approx(shares["engine"])


def test_prefill_device_time_apart_from_decode():
    assert reader("prefill_device_ms.serve").read(device_obs()) \
        == pytest.approx(103.0)


# ------------- scopes: a crafted capture (two steps of nine operations)

SCOPED = os.path.join(HERE, "data", "scoped_ops.xplane.pb")


@pytest.fixture
def scoped(tmp_path, monkeypatch):
    """A trace made by hand with the generated protobuf classes: the
    plane ``/host:metadata`` holds the program ``jit_train_step(7)`` with
    a name path on eight of its nine instructions, ``/device:TPU:0`` two
    executions of it (100 ms each: attention 50, lm_head 10 + 5, loss 5 +
    5, optimizer 4, a scope that only resembles one 11, a flash_fwd
    kernel 8, a copy without a path 2) and one of another program that
    reuses an instruction's name."""
    monkeypatch.setattr(ps, "BENCH_OUT", str(tmp_path))
    target = tmp_path / "cpu.cell" / "trace" / "plugins" / "profile" / "x"
    target.mkdir(parents=True)
    shutil.copy(SCOPED, target / "vm.xplane.pb")
    trace = load(SCOPED)
    return Obs(trace=trace, trace_window=window_of(trace),
               cell={"name": "cpu.cell"})


def test_scope_shares_of_the_train_step(scoped, capsys):
    assert reader("head_loss_step_share.train").read(scoped) \
        == pytest.approx(25.0)
    assert reader("optimizer_step_share.train").read(scoped) \
        == pytest.approx(4.0)
    assert ps.scope_step_share(scoped, ("flash_fwd",)) == pytest.approx(8.0)
    assert ps.scope_step_share(scoped, ("no_such_scope",)) is None
    said = capsys.readouterr().out
    assert "scope lm_head: 15.000 ms a step in 2 operations" in said
    assert "operations with no name path: 2.000 ms a step of 100.000" in said


def test_name_paths_are_read_from_the_traces_own_modules(scoped):
    from benchmark.harness import hlo_names
    names = hlo_names.op_names(SCOPED)
    assert list(names) == ["jit_train_step(7)"]
    assert names["jit_train_step(7)"]["custom-call.8"].endswith(
        "/attn/flash_fwd/flash_fwd/pallas_call")
    assert "copy.9" not in names["jit_train_step(7)"]
    assert hlo_names.instruction_of(
        "%fusion.12 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kLoop") \
        == "fusion.12"
    # the other program's %fusion.6 is not the step's optimizer
    assert len(ps.step_ops(scoped)) == 18


def test_the_wire_reader_agrees_with_the_generated_classes():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    from benchmark.harness import hlo_names
    space = xplane_pb2.XSpace()
    with open(SCOPED, "rb") as f:
        space.ParseFromString(f.read())
    want = {}
    for plane in space.planes:
        if plane.name == hlo_names.METADATA_PLANE:
            for md in plane.event_metadata.values():
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(md.stats[0].bytes_value)
                want[md.name] = {
                    i.name: i.metadata.op_name
                    for c in proto.hlo_module.computations
                    for i in c.instructions if i.metadata.op_name}
    assert hlo_names.op_names(SCOPED) == want and len(want[
        "jit_train_step(7)"]) == 8


# ------------------------------------------------ the recorded capture

@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """The fixture where run.py would have written a cell's trace."""
    monkeypatch.setattr(ps, "BENCH_OUT", str(tmp_path))
    target = tmp_path / "cpu.cell" / "trace" / "plugins" / "profile" / "x"
    target.mkdir(parents=True)
    shutil.copy(FIXTURE, target / "vm.xplane.pb")
    trace = load(FIXTURE, host_names={"bench.window"})
    return Obs(trace=trace, trace_window=window_of(trace),
               cell={"name": "cpu.cell"})


def test_recorded_capture_holds_the_programs_annotations(recorded):
    counts = {}
    for e in ps.annotations(recorded):
        counts[e.name] = counts.get(e.name, 0) + 1
    assert counts == {
        "llm.step": 7, "llm.step.admit": 7, "llm.step.decode": 6,
        "llm.step.prefill": 2, "llm.step.commit": 8,
        "runner.build_inputs": 8, "runner.dispatch": 8, "runner.fetch": 8}
    steps = ps.annotated(recorded, "llm.step")
    assert all(any(s.start <= e.start and e.end <= s.end for s in steps)
               for e in ps.annotations(recorded))
    # no device plane on the CPU: the device readers have nothing to read
    assert reader("prefill_device_ms.serve").read(recorded) is None
    assert reader("idle_in_engine_share.serve").read(recorded) is None
    assert reader("feed_host_batch_p50_ms.train").read(recorded) is None


def test_feed_annotations_are_read_from_the_capture(recorded):
    recorded._program_annotations = [
        Event("data.feed.host_batch", 0, 300_000),
        Event("data.feed.device_put", 300_000, 100_000),
        Event("data.feed.host_batch", 10 * MS, 500_000),
        Event("data.feed.host_batch", 20 * MS, 900_000)]
    assert reader("feed_host_batch_p50_ms.train").read(recorded) \
        == pytest.approx(0.5)
