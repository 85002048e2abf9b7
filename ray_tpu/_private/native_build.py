"""Build-on-load for the native libraries (``src/`` -> ``ray_tpu/core``).

The binaries are not committed (``*.so`` is ignored), so a checkout
builds them on first use. Whether a binary is current is decided by the
source's CONTENT — a ``<lib>.so.sha256`` stamp written beside it — not
by mtimes, which a copied or archived tree does not preserve: a stale
binary is never loaded.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile
from typing import Dict

_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# library file name -> "built" (compiled from src/ by this process) or
# "loaded" (a binary whose stamp matched the source)
STATUS: Dict[str, str] = {}


def _replace(path: str, write) -> None:
    # temp + atomic rename: many raylet/worker processes may race to
    # build on a fresh checkout
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))
    os.close(fd)
    try:
        write(tmp)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def ensure_built(lib_name: str, src_rel: str, *link_flags: str) -> str:
    """Path of ``ray_tpu/core/<lib_name>``, compiled from
    ``src/<src_rel>`` unless its stamp already matches that source.
    Raises if it has to build and cannot (no ``g++``)."""
    out = os.path.join(_PKG, "core", lib_name)
    src = os.path.join(os.path.dirname(_PKG), "src", src_rel)
    stamp = out + ".sha256"
    if not os.path.exists(src) and os.path.exists(out):
        STATUS.setdefault(lib_name, "loaded")  # installed without src/
        return out
    with open(src, "rb") as f:
        want = hashlib.sha256(f.read()).hexdigest()
    try:
        with open(stamp) as f:
            have = f.read().strip()
    except OSError:
        have = None
    if have == want and os.path.exists(out):
        STATUS.setdefault(lib_name, "loaded")
        return out
    os.makedirs(os.path.dirname(out), exist_ok=True)
    _replace(out, lambda tmp: subprocess.check_call(
        ["g++", "-O2", "-fPIC", "-shared", "-o", tmp, src, *link_flags]))
    os.chmod(out, 0o755)

    def write_stamp(tmp):
        with open(tmp, "w") as f:
            f.write(want + "\n")
    _replace(stamp, write_stamp)
    STATUS[lib_name] = "built"
    return out
