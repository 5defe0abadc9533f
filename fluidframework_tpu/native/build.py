"""Build the native components (g++ → shared libraries for ctypes).

Usage: ``python -m fluidframework_tpu.native.build`` or import
``ensure_built()`` for build-on-demand (used by the ctypes wrappers).

A library is a function of the committed sources and the compile flags
only: its file name carries a hash of both (``libdeli.<hash>.so``), so a
binary left on disk by an earlier tree can never be the one that loads —
a changed ``.cpp`` is a different file name, whatever the mtimes say.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))

TARGETS = {
    "libdeli.so": ["sequencer.cpp"],
    "liboplog.so": ["oplog.cpp"],
    "libingress.so": ["ingress.cpp"],
}

CXX = "g++"
FLAGS = ("-O2", "-std=c++17", "-shared", "-fPIC")


class NativeBuildError(RuntimeError):
    """A native library could not be built; carries the compiler's words."""


#: build path → the error that build raised (a missing compiler stays
#: missing: ``available()`` probes must not re-run the failing command on
#: every call; changed sources are a new path and a new attempt)
_failed: Dict[str, NativeBuildError] = {}


def built_path(target: str, src_dir: str = HERE) -> str:
    """Where ``target`` built from the sources as they are NOW lives."""
    h = hashlib.sha256(" ".join((CXX,) + FLAGS).encode())
    for src in TARGETS[target]:
        h.update(b"\0" + src.encode() + b"\0")
        with open(os.path.join(src_dir, src), "rb") as f:
            h.update(f.read())
    stem = target[:-len(".so")]
    return os.path.join(src_dir, f"{stem}.{h.hexdigest()[:16]}.so")


def ensure_built(target: str = "libdeli.so", src_dir: str = HERE) -> str:
    """Path to ``target`` built from the current sources, compiling it
    first unless that exact build is already on disk. Raises
    ``NativeBuildError`` (with the compiler's stderr) when it cannot."""
    out = built_path(target, src_dir)
    if os.path.exists(out):
        return out
    if out in _failed:
        raise _failed[out]
    srcs = [os.path.join(src_dir, s) for s in TARGETS[target]]
    tmp = f"{out}.{os.getpid()}.tmp"   # concurrent importers: atomic rename
    cmd = [CXX, *FLAGS, "-o", tmp, *srcs]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, "stderr", None) or str(e)
        err = NativeBuildError(
            f"cannot build {target}: `{' '.join(cmd)}` failed:\n{detail}")
        _failed[out] = err
        raise err from e
    os.replace(tmp, out)
    # builds of other source versions (and the pre-hash plain name) are dead
    stem = os.path.join(src_dir, target[:-len(".so")])
    for stale in glob.glob(stem + ".*.so") + [stem + ".so"]:
        if stale != out:
            try:
                os.unlink(stale)
            except FileNotFoundError:   # absent, or a concurrent builder won
                pass
    return out


if __name__ == "__main__":
    for t in TARGETS:
        print(f"{t}: built at {ensure_built(t)}")
