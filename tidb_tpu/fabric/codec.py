"""Length-prefixed frame codec for the compile-server socket protocol.

One frame = 4-byte magic + 4-byte big-endian payload length + payload.
The payload is a pickled dict (a TRUSTED same-host protocol: the socket
is a 0700-dir unix socket or loopback TCP owned by the fleet — never an
exposed surface; pickle keeps numpy/bytes payloads zero-ceremony).

The codec is deliberately strict — the failure mode it guards against is
a peer that dies or stalls mid-reply, so every torn read is a loud
:class:`FrameError`, never a silent partial object:

* short read mid-header or mid-payload -> FrameError (how many bytes
  arrived vs expected — the post-mortem that distinguishes "server died
  mid-reply" from "nothing ever listened");
* wrong magic -> FrameError (a non-protocol peer, or a stream that lost
  sync);
* length over :data:`MAX_FRAME` -> FrameError before any allocation (a
  corrupt length must not OOM the reader).

Callers map FrameError to the classified transport taxonomy
(utils/backoff.classify -> ``transport``), so a torn frame walks the
same retry/breaker ladder as a dead connection.
"""

from __future__ import annotations

import io
import pickle
import struct

MAGIC = b"TFCS"
#: largest accepted payload (serialized StableHLO modules for the big
#: TPC-H fragments run ~1-10MB; 256MB is a corruption bound, not a goal)
MAX_FRAME = 256 << 20

_HDR = struct.Struct("!4sI")


class FrameError(Exception):
    """A torn, truncated or out-of-protocol frame (classified
    ``transport`` by utils/backoff.classify via ConnectionError)."""


def _recv_exact(sock, n: int) -> bytes:
    """Read exactly n bytes from a socket or file-like; FrameError on a
    short read (peer died mid-frame)."""
    buf = bytearray()
    recv = getattr(sock, "recv", None)
    while len(buf) < n:
        chunk = (recv(n - len(buf)) if recv is not None
                 else sock.read(n - len(buf)))
        if not chunk:
            raise FrameError(
                f"short read: got {len(buf)} of {n} expected bytes "
                "(peer closed mid-frame)")
        buf.extend(chunk)
    return bytes(buf)


def write_frame(sock, obj: dict) -> None:
    payload = pickle.dumps(obj, protocol=4)
    if len(payload) > MAX_FRAME:
        raise FrameError(f"frame too large: {len(payload)} > {MAX_FRAME}")
    data = _HDR.pack(MAGIC, len(payload)) + payload
    send = getattr(sock, "sendall", None)
    if send is not None:
        send(data)
    else:
        sock.write(data)


def read_frame(sock) -> dict:
    hdr = _recv_exact(sock, _HDR.size)
    magic, length = _HDR.unpack(hdr)
    if magic != MAGIC:
        raise FrameError(f"bad frame magic {magic!r} (not a compile-server "
                         "peer, or the stream lost sync)")
    if length > MAX_FRAME:
        raise FrameError(f"frame length {length} exceeds {MAX_FRAME} "
                         "(corrupt header)")
    payload = _recv_exact(sock, length)
    try:
        obj = pickle.loads(payload)
    except Exception as e:
        raise FrameError(f"undecodable frame payload: {e}") from e
    if not isinstance(obj, dict):
        raise FrameError(f"frame payload is {type(obj).__name__}, "
                         "expected dict")
    return obj


def frame_bytes(obj: dict) -> bytes:
    """The on-wire bytes of one frame (tests build torn variants)."""
    out = io.BytesIO()
    write_frame(out, obj)
    return out.getvalue()
