"""The door's wire format, as a client sees it (a copy: the generator and
the checks must not import the program, and a later PR may change the
program's own encoder but not this yardstick).

Frame: u8 type | u32 len | payload | u32 crc32(payload), little-endian.
``J`` = JSON control, ``B`` = op batch (text table + 16-byte records),
``R`` = rich op batch (text table + props table + records).
"""

import json
import struct
import zlib

import numpy as np

HDR = struct.Struct("<BI")
OP_DTYPE = np.dtype([("row", "<u2"), ("kind", "u1"), ("a0", "<u2"),
                     ("a1", "<u2"), ("tidx", "u1"), ("cseq", "<u4"),
                     ("ref", "<u4")])
assert OP_DTYPE.itemsize == 16
INS, REM, ANN = 0, 1, 2


def encode_frame(ftype: bytes, payload: bytes) -> bytes:
    return HDR.pack(ftype[0], len(payload)) + payload + \
        struct.pack("<I", zlib.crc32(payload))


def encode_json(obj) -> bytes:
    return encode_frame(b"J", json.dumps(obj).encode())


def table_prefix(texts, props=None) -> bytes:
    """The part of an op frame's payload that precedes the records: the
    text table and, for a rich frame, the props table."""
    parts = [bytes([len(texts)])]
    for t in texts:
        b = t.encode()
        parts += [struct.pack("<H", len(b)), b]
    if props is not None:
        parts.append(bytes([len(props)]))
        for p in props:
            b = json.dumps(p).encode()
            parts += [struct.pack("<H", len(b)), b]
    return b"".join(parts)


def frame_tables(ops: np.ndarray, texts, props=None):
    """What one frame carries, as a client library sends it: the distinct
    texts its inserts use and the distinct marks of its annotates, and the
    records with ``tidx`` counted in those tables. ``ops['tidx']`` comes
    in as an index into the whole vocabulary (``texts`` / ``props``).
    Returns (payload prefix, records)."""
    out = ops.copy()
    ins = ops["kind"] == INS
    used, inv = np.unique(ops["tidx"][ins], return_inverse=True)
    out["tidx"][ins] = inv
    marks = None
    if props is not None:
        ann = ops["kind"] == ANN
        used_p, inv = np.unique(ops["tidx"][ann], return_inverse=True)
        out["tidx"][ann] = inv
        marks = [props[i] for i in used_p.tolist()]
    return table_prefix([texts[i] for i in used.tolist()], marks), out


def encode_ops(prefix: bytes, ops: np.ndarray, rich: bool) -> bytes:
    return encode_frame(b"R" if rich else b"B",
                        prefix + np.ascontiguousarray(ops).tobytes())


def split_frames(buf: bytearray):
    """Pop every complete frame off the front of ``buf``; returns
    [(type, payload bytes)]. A crc mismatch raises."""
    out = []
    off, n = 0, len(buf)
    while n - off >= 5:
        ftype, length = HDR.unpack_from(buf, off)
        total = 5 + length + 4
        if n - off < total:
            break
        payload = bytes(buf[off + 5:off + 5 + length])
        (crc,) = struct.unpack_from("<I", buf, off + 5 + length)
        if crc != zlib.crc32(payload):
            raise IOError("frame CRC mismatch")
        out.append((ftype, payload))
        off += total
    if off:
        del buf[:off]
    return out


def stream_checksum(row, seq, client, cseq, kind, a0, a1, tidx):
    """Order-free checksum of a set of sequenced ops: count, and the sum
    of a 64-bit mix of each op's fields (wrapping)."""
    with np.errstate(over="ignore"):
        h = np.zeros(len(row), np.uint64)
        for f in (row, seq, client, cseq, kind, a0, a1, tidx):
            h = (h ^ np.asarray(f).astype(np.uint64)) \
                * np.uint64(0x9E3779B97F4A7C15)
            h ^= h >> np.uint64(29)
        return np.asarray([len(row), int(h.sum(dtype=np.uint64))],
                          np.uint64)
