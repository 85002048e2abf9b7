"""What the driver process may know of the chip without touching JAX."""

from __future__ import annotations

import os
import time
from typing import Dict, List


def chip_files(pid="self") -> List[str]:
    held = set()
    try:
        fds = os.listdir(f"/proc/{pid}/fd")
    except OSError:
        return []
    for fd in fds:
        try:
            path = os.readlink(f"/proc/{pid}/fd/{fd}")
        except OSError:
            continue
        d, name = os.path.split(path)
        if (d == "/dev/vfio" and name.isdigit()) or (
                d == "/dev" and name.startswith("accel")):
            held.add(path)
    return sorted(held)


def chips_present() -> int:
    n = 0
    try:
        n += sum(1 for f in os.listdir("/dev/vfio") if f.isdigit())
    except OSError:
        pass
    try:
        n += sum(1 for f in os.listdir("/dev") if f.startswith("accel"))
    except OSError:
        pass
    return n


def holders() -> Dict[int, List[str]]:
    held = {}
    for pid in os.listdir("/proc"):
        if pid.isdigit():
            files = chip_files(pid)
            if files:
                held[int(pid)] = files
    return held


def wait_chip_free(timeout_s: float = 120.0) -> bool:
    deadline = time.time() + timeout_s
    while holders():
        if time.time() > deadline:
            return False
        time.sleep(0.2)
    return True


def device_report(chips_wanted: int, rehearse: bool) -> Dict:
    """Inside the process that owns the chip: the device as JAX reports
    it. Anything but the TPU the cell asks for is an error."""
    import jax
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise RuntimeError(
                f"pid {os.getpid()} runs on {devs[0].platform!r}, not on "
                "a TPU")
        if len(devs) != chips_wanted:
            raise RuntimeError(
                f"the cell asks for {chips_wanted} chip(s), JAX sees "
                f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use") or 0))
    return peak
