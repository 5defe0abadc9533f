"""ctypes binding for the native ingress drain (frame scan + op gather).

The batch front door (``server.columnar_ingress``) accumulates raw recv
chunks per connection and decodes whole buffers per drain pass. The two
byte-bound stages of that pass — splitting the buffer into CRC-verified
frames and gathering 16-byte op records into int32 planes — have a C++
fast path (``native/ingress.cpp``, built on demand by ``native/build.py``)
with the numpy implementations in ``columnar_ingress`` as the
always-available fallback; same layering as ``native_deli`` /
``native_oplog``.

``available()`` says whether the library can be built here;
``scan``/``gather`` raise the build error when called without it, so
callers gate on ``available()``.
"""

from __future__ import annotations

import ctypes
from typing import List, Tuple

import numpy as np

from ..native.build import NativeBuildError, ensure_built

_lib = None

#: defensive bound on one frame's payload (matches wire.MAX_FRAME)
MAX_PAYLOAD = 64 * 1024 * 1024

#: scan stop reasons beyond a clean split (status 1 / 2)
SCAN_BAD_CRC = 1
SCAN_TOO_LARGE = 2

_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)


def _load():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(ensure_built("libingress.so"))
    lib.ingress_scan.restype = None
    lib.ingress_scan.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, _I64P, _I64P, _I32P]
    lib.ingress_gather.restype = None
    lib.ingress_gather.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_void_p] + [ctypes.c_void_p] * 7
    _lib = lib
    return lib


def available() -> bool:
    try:
        _load()
    except NativeBuildError:
        return False
    return True


def require() -> None:
    """Raise the build error unless the library loads (``decode="native"``)."""
    _load()


def scan(buf) -> Tuple[List[Tuple[int, int, int]], int, int]:
    """Split ``buf`` (bytes-like) into complete CRC-valid frames.

    Returns ``(frames, consumed, status)``: ``frames`` is a list of
    ``(ftype, payload_off, payload_len)`` triples, ``consumed`` the bytes
    they cover (a trailing partial frame stays unconsumed), ``status``
    0 = clean / SCAN_BAD_CRC / SCAN_TOO_LARGE — on a non-zero status the
    scan stopped AT the poisoned frame; the good prefix is still
    returned. Contract (and fallback) live in
    ``columnar_ingress.split_frames``."""
    lib = _load()
    arr = np.frombuffer(buf, np.uint8)
    n = arr.size
    cap = n // 9 + 1  # min frame = 5B header + 4B crc
    ftype = np.empty(cap, np.uint8)
    poff = np.empty(cap, np.int64)
    plen = np.empty(cap, np.int64)
    n_frames = ctypes.c_int64()
    consumed = ctypes.c_int64()
    status = ctypes.c_int32()
    lib.ingress_scan(
        arr.ctypes.data_as(ctypes.c_void_p), n, MAX_PAYLOAD, cap,
        ftype.ctypes.data_as(ctypes.c_void_p),
        poff.ctypes.data_as(ctypes.c_void_p),
        plen.ctypes.data_as(ctypes.c_void_p),
        ctypes.byref(n_frames), ctypes.byref(consumed),
        ctypes.byref(status))
    k = n_frames.value
    frames = list(zip(ftype[:k].tolist(), poff[:k].tolist(),
                      plen[:k].tolist()))
    return frames, consumed.value, status.value


def gather(buf, runs: List[Tuple[int, int]]) -> dict:
    """Gather op records from ``runs`` (``(byte_off, record_count)`` per
    op frame, in frame order) into seven contiguous int32 planes.
    Returns ``{"row", "kind", "a0", "a1", "tidx", "cseq", "ref"}``."""
    lib = _load()
    arr = np.frombuffer(buf, np.uint8)
    roff = np.array([r[0] for r in runs], np.int64)
    rcnt = np.array([r[1] for r in runs], np.int64)
    total = int(rcnt.sum()) if runs else 0
    planes = {name: np.empty(total, np.int32)
              for name in ("row", "kind", "a0", "a1", "tidx", "cseq",
                           "ref")}
    if total:
        lib.ingress_gather(
            arr.ctypes.data_as(ctypes.c_void_p), len(runs),
            roff.ctypes.data_as(ctypes.c_void_p),
            rcnt.ctypes.data_as(ctypes.c_void_p),
            *[planes[k].ctypes.data_as(ctypes.c_void_p)
              for k in ("row", "kind", "a0", "a1", "tidx", "cseq",
                        "ref")])
    return planes
