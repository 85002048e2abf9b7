#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
and, traced, ``breakdown``. Without the chips the cell asks for the
command exits non-zero and prints no such line.

This process never initialises a JAX backend: the chip belongs to the
train worker or the serve replica, which has exited before the line is
printed. Builder's options (not used by the driver): ``--rehearse`` (tiny
sizes on the CPU, no result line, exit 3), ``--seeds a,b,c`` (serve
cells: several seeds after one set-up), ``--control`` (also compute the
lower-precision control of the correctness check), ``--rates a,b,c`` (open loop:
a sweep of other rates than the traffic file's after one set-up), ``--describe-trace``.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import shutil    # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HOST_SPANS = ("bench.window", "adapter.decode", "adapter.prefill",
              "next(feed)", "step")


def log(msg):
    print(f"[{time.time() - T_PROCESS_START:7.1f}s] {msg}", flush=True)


class Observation:
    """What a per-layer metric's reader may read."""

    def __init__(self, cell, result, peaks, trace, trace_window):
        self.cell = cell
        self.peaks = peaks
        self.trace = trace
        self.trace_window = trace_window
        self.__dict__.update(result["observations"])


def reduce_trace(trace_dir, describe, rehearse):
    from benchmark.harness import xplane
    path = xplane.find_xplane(trace_dir)
    trace = xplane.load(path, host_names=set(HOST_SPANS))
    window = xplane.window_of(trace)
    if describe:
        log("trace by hand:\n" + xplane.describe(trace, window))
    busy_s = xplane.busy_seconds(trace, window)
    if busy_s <= 0 and not rehearse:
        raise RuntimeError("the traced window holds no device operation")
    labels = [s for s in HOST_SPANS if s != "bench.window"]
    breakdown = {
        "device_ops": [[n, s] for n, s in xplane.top_ops(trace, window)],
        "idle_gaps": [[n, s] for n, s in xplane.idle_gaps(
            trace, window, labels)]}
    return trace, window, busy_s, breakdown


def dump_worker_logs(lines=25):
    """The end of every log this process's cluster wrote."""
    import glob
    import tempfile
    pattern = os.path.join(tempfile.gettempdir(), "ray_tpu",
                           f"session_*_{os.getpid()}_*", "logs", "*")
    for path in sorted(glob.glob(pattern)):
        if not os.path.isfile(path):
            continue
        with open(path, errors="replace") as f:
            tail = [ln.rstrip()[:400] for ln in f.readlines()[-lines:]
                    if ln.strip()]
        if tail:
            log(f"---- {os.path.basename(path)} (last {len(tail)} lines)")
            for ln in tail:
                print("    " + ln, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--describe-trace", action="store_true")
    args = ap.parse_args()

    from benchmark.harness import cells, chips
    cell = cells.load_cell(args.workload)
    asked = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse:
        if asked and "tpu" not in asked.split(","):
            sys.exit(f"benchmark: JAX_PLATFORMS={asked} keeps JAX off the "
                     "TPU; a cell is measured on the chip or not at all")
        have = chips.chips_present()
        if have < cell["chips"]:
            sys.exit(f"benchmark: the cell asks for {cell['chips']} chip(s)"
                     f", this machine shows {have}")
    # this process stays off the chip; workers granted a chip get their
    # platform from the raylet
    os.environ["JAX_PLATFORMS"] = "cpu"
    # one compile cache inside the checkout (where the environment names
    # none), keyed without call stacks, holding every program however
    # quick its compile: the second run of a cell compiles nothing
    from ray_tpu.common.config import compile_cache_env
    compile_cache_env(os.environ)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    # settings of the program that the configuration states, in the
    # environment its processes inherit
    for k, v in cell["config_data"].get("environment", {}).items():
        os.environ.setdefault(k, str(v))
    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)

    import ray_tpu
    runner = cells.runner_module(cell)
    ctx = {"cell": cell, "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "trace_dir": trace_dir,
           "rehearse": args.rehearse, "log": log, "control": args.control,
           "rates": [float(r) for r in args.rates.split(",") if r],
           "seeds": [int(s) for s in args.seeds.split(",") if s]}
    log(f"cell {cell['name']}: seed {args.seed}, {args.seconds:g}s, "
        f"trace {args.trace}" + (", REHEARSAL on the CPU" if args.rehearse
                                 else ""))
    ray_tpu.init(num_cpus=8, object_store_memory=2 * 1024**3,
                 _system_config=dict(
                     cell["config_data"].get("system_config", {}),
                     prestart_workers=False))
    try:
        if not args.rehearse:
            tpus = int(ray_tpu.cluster_resources().get("TPU", 0))
            if tpus < cell["chips"]:
                sys.exit(f"benchmark: the cluster found {tpus} TPU chip(s)"
                         f", the cell asks for {cell['chips']}")
        result = runner.run(ctx)
    except Exception:
        # a failed set-up or a harness fault: say what the cluster's own
        # processes wrote, since that is where the cause is
        import traceback
        traceback.print_exc()
        dump_worker_logs()
        sys.exit("benchmark: the run failed before it had a result")
    finally:
        ray_tpu.shutdown()
    if not chips.wait_chip_free(60.0):
        log(f"the chip is still held by {chips.holders()}")
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            sys.exit("benchmark: the driver process initialised a JAX "
                     "backend")

    setup_s = result["window"][0] - T_PROCESS_START
    device = dict(result["device"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {},
            "device": device}
    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    if not args.trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is None:
                sys.exit(f"benchmark: no value for {m['name']}")
            line["metrics"][m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        peaks = None if args.rehearse else cells.peaks_for(device["kind"])
        trace, window, busy_s, breakdown = reduce_trace(
            trace_dir, args.describe_trace, args.rehearse)
        device["busy_s"] = busy_s
        device["window_s"] = (window[1] - window[0]) / 1e9
        line["breakdown"] = breakdown
        obs = Observation(cell, result, peaks, trace, window)
        for reader in cells.layer_metric_readers(cell):
            value = reader.read(obs)
            if value is not None:
                line["metrics"][reader.NAME] = {
                    "value": float(value), "unit": units[reader.NAME]}
        log(f"also, untraced definitions on this traced run: "
            f"{dict(result['end_to_end'], setup_s=setup_s)}")
    for k, v in line["metrics"].items():
        log(f"{k} = {v['value']!r} {v['unit']}")
    log(f"whole run {time.time() - T_PROCESS_START:.1f}s, set-up "
        f"{setup_s:.1f}s, device {device}")
    if args.rehearse:
        log("rehearsal passed: no result line, exit 3")
        sys.exit(3)
    if ctx["seeds"]:
        with open(os.path.join(out_dir, "seeds.json"), "w") as f:
            json.dump(result["observations"].get("all_runs"), f)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
