"""TPU chip/topology detection (raylet.py detect_tpu_chips).

Reference analogue: _private/resource_spec.py accelerator
autodetection tests.
"""

import os
from unittest import mock

import pytest

from ray_tpu._private.raylet import (_chips_from_accel_type,
                                     detect_tpu_chips, detect_tpu_topology)
from ray_tpu.common.config import SystemConfig


def _cfg(chips=-1):
    c = SystemConfig()
    c.tpu_chips_per_host = chips
    return c


def test_explicit_config_wins():
    with mock.patch.dict(os.environ, {"RTPU_NUM_TPUS": "7"}):
        assert detect_tpu_chips(_cfg(chips=2)) == 2


def test_env_override():
    with mock.patch.dict(os.environ, {"RTPU_NUM_TPUS": "3"}):
        assert detect_tpu_chips(_cfg()) == 3


def test_granted_chips_env():
    env = {"TPU_VISIBLE_CHIPS": "0,1,2", "RTPU_NUM_TPUS": ""}
    env.pop("RTPU_NUM_TPUS")
    with mock.patch.dict(os.environ, env, clear=False):
        os.environ.pop("RTPU_NUM_TPUS", None)
        assert detect_tpu_chips(_cfg()) == 3
    # empty grant = zero chips (a worker fenced off from the TPU)
    with mock.patch.dict(os.environ, {"TPU_VISIBLE_CHIPS": ""}):
        os.environ.pop("RTPU_NUM_TPUS", None)
        assert detect_tpu_chips(_cfg()) == 0


def test_accel_type_parsing():
    # v5e counts chips directly
    assert _chips_from_accel_type("v5litepod-8") == 8
    # v4 counts cores (2 per chip); without TPU_WORKER_HOSTNAMES the
    # per-host physical ceiling (4 chips on v4) caps the guess so a
    # multi-host slice can't be mistaken for one 16-chip host
    assert _chips_from_accel_type("v4-32") == 4
    with mock.patch.dict(os.environ, {
            "TPU_WORKER_HOSTNAMES": "h0,h1,h2,h3"}):
        assert _chips_from_accel_type("v4-32") == 4
    assert _chips_from_accel_type("bogus") is None


def test_accel_type_divided_across_hosts():
    with mock.patch.dict(os.environ, {
            "TPU_WORKER_HOSTNAMES": "host-0,host-1"}):
        assert _chips_from_accel_type("v5litepod-16") == 8


def test_accel_type_env_fallback():
    env = {"TPU_ACCELERATOR_TYPE": "v5litepod-4",
           "TPU_SKIP_MDS_QUERY": "1"}
    with mock.patch.dict(os.environ, env):
        for k in ("RTPU_NUM_TPUS", "TPU_VISIBLE_CHIPS",
                  "TPU_VISIBLE_DEVICES"):
            os.environ.pop(k, None)
        with mock.patch("os.path.isdir", return_value=False):
            assert detect_tpu_chips(_cfg()) == 4


def _listing(files):
    def listdir(path):
        if path in files:
            return files[path]
        raise FileNotFoundError(path)
    return mock.patch("os.listdir", side_effect=listdir)


@pytest.mark.parametrize("dev,want", [
    # a v5e host: one numbered file per chip beside the control node; the
    # /dev/vfio directory itself is not a chip
    ({"/dev": ["null", "vfio"], "/dev/vfio": ["3", "vfio"]}, 1),
    ({"/dev": ["null", "vfio"],
      "/dev/vfio": ["0", "1", "2", "3", "vfio"]}, 4),
    ({"/dev": ["accel0", "accel1", "accelerometer", "vfio"],
      "/dev/vfio": ["vfio"]}, 2),
    ({"/dev": ["null"]}, 0),
], ids=["vfio-1", "vfio-4", "accel-2", "none"])
def test_device_files_count_chips(dev, want):
    env = {"TPU_SKIP_MDS_QUERY": "1"}
    with mock.patch.dict(os.environ, env), _listing(dev):
        for k in ("RTPU_NUM_TPUS", "TPU_VISIBLE_CHIPS",
                  "TPU_VISIBLE_DEVICES", "TPU_ACCELERATOR_TYPE",
                  "JAX_PLATFORMS"):
            os.environ.pop(k, None)
        assert detect_tpu_chips(_cfg()) == want


def test_declared_topology_caps_device_files():
    env = {"TPU_ACCELERATOR_TYPE": "v5litepod-4",
           "TPU_SKIP_MDS_QUERY": "1"}
    dev = {"/dev": ["vfio"],
           "/dev/vfio": [str(i) for i in range(8)] + ["vfio"]}
    with mock.patch.dict(os.environ, env), _listing(dev):
        for k in ("RTPU_NUM_TPUS", "TPU_VISIBLE_CHIPS",
                  "TPU_VISIBLE_DEVICES"):
            os.environ.pop(k, None)
        assert detect_tpu_chips(_cfg()) == 4


@pytest.mark.parametrize("said,index", [
    ("3", 3), (None, 0),
    # what libtpu leaves in the environment of a process that loaded it
    # where no worker number can be found (tests/test_chip_compile.py
    # does), and a driver started from that process inherits: a raylet
    # that died of it failed whichever test shared the pytest worker
    ("WARNING: could not determine TPU worker number, please set env "
     "var `TPU_WORKER_ID` manually, otherwise libtpu.so may not "
     "properly initialize.", 0)], ids=["number", "unset", "libtpu-text"])
def test_worker_index_from_the_environment(said, index):
    env = {} if said is None else {"TPU_WORKER_ID": said}
    with mock.patch.dict(os.environ, env):
        if said is None:
            os.environ.pop("TPU_WORKER_ID", None)
        assert detect_tpu_topology()["worker_index"] == index
