"""The serve frontend's framing, as the benchmark's client speaks it.

A copy of the codec in ``byteps_tpu/engine/wire.py`` (``_encode`` /
``_decode_frame``) cut down to what a streaming client needs, so the
load generator imports neither JAX nor the program and parses frames
from a non-blocking socket's buffer:

    u8 op | u32 len(name) | name | u32 len(dtype) | dtype |
    u8 ndim | u64 x ndim shape | u64 len(payload) | payload

A request's ``op`` is the wire op (3 = STREAM); a reply's is its status
(0 ok, 1 typed error whose message is the payload).  STREAM replies are
one ``"t"`` frame per token, then an ``"end"`` frame.
"""

from __future__ import annotations

import json
import struct
from typing import Optional, Tuple

import numpy as np

OP_STATS, OP_STREAM = 1, 3


def encode_stream_request(prompt: np.ndarray, max_new_tokens: int,
                          seed: int = 0) -> bytes:
    name = json.dumps({"max_new_tokens": int(max_new_tokens),
                       "seed": int(seed), "priority": 0,
                       "resume": 0}).encode()
    arr = np.ascontiguousarray(prompt, dtype=np.int32)
    dt = b"int32"
    return b"".join([
        struct.pack("<BI", OP_STREAM, len(name)), name,
        struct.pack("<I", len(dt)), dt,
        struct.pack("<BQ", 1, arr.shape[0]),
        struct.pack("<Q", arr.nbytes), arr.tobytes()])


def encode_stats_request() -> bytes:
    return (struct.pack("<BI", OP_STATS, 0) + struct.pack("<I", 0)
            + struct.pack("<B", 0) + struct.pack("<Q", 0))


def parse_frame(buf, start: int = 0
                ) -> Optional[Tuple[int, str, int, bytes, int]]:
    """One frame out of ``buf[start:]``: ``(status, name, n_items,
    payload, next_offset)``, or ``None`` while the frame is incomplete.
    ``n_items`` is the element count of the array a frame carries."""
    n = len(buf)
    p = start
    if n - p < 5:
        return None
    status, nlen = struct.unpack_from("<BI", buf, p)
    p += 5
    if n - p < nlen + 4:
        return None
    name = bytes(buf[p:p + nlen]).decode()
    p += nlen
    (dlen,) = struct.unpack_from("<I", buf, p)
    p += 4
    if n - p < dlen + 1:
        return None
    p += dlen
    ndim = buf[p]
    p += 1
    if n - p < 8 * ndim + 8:
        return None
    shape = struct.unpack_from(f"<{ndim}Q", buf, p) if ndim else ()
    p += 8 * ndim
    (plen,) = struct.unpack_from("<Q", buf, p)
    p += 8
    if n - p < plen:
        return None
    payload = bytes(buf[p:p + plen])
    items = int(np.prod(shape)) if ndim else 0
    return status, name, items, payload, p + plen
