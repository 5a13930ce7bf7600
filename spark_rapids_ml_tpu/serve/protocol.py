"""Wire framing for the data-plane daemon.

Every message is a 4-byte big-endian length prefix + payload. A request is
one JSON frame, optionally followed by one Arrow IPC stream frame (op
"feed"). A response is one JSON frame, optionally followed by raw-buffer
frames for each array listed in the JSON's ``arrays`` spec (op
"finalize"). Max frame size bounds a malformed/hostile length prefix.

**The protocol is FROZEN at PROTOCOL_VERSION** (see ``docs/protocol.md``
for the full op-by-op frame contract — the document third-party clients,
e.g. a Scala/JVM implementation, build against). Every request carries a
``"v"`` field; the daemon rejects mismatches with a message naming the
version it speaks. ``ping`` is version-exempt and echoes the server
version, so a client can discover it before committing to a dialect.
Any change to frames, fields, or semantics of existing ops bumps the
version; additive new ops keep it. ``tests/test_protocol_golden.py``
replays a recorded v1 byte transcript against a live daemon — if that
test fails, the frozen contract broke.

The serving scheduler (serve/scheduler.py) is invisible at this layer by
design: micro-batched ``transform``/``kneighbors`` responses are
byte-identical to solo ones, and the additive ``warmup`` op is a plain
JSON round-trip — no new framing shapes.
"""

from __future__ import annotations

import json
import struct
from typing import Any, Dict, Optional

import numpy as np

from spark_rapids_ml_tpu.utils import faults
from spark_rapids_ml_tpu.utils import metrics as _metrics

#: Wire-level byte accounting (length prefixes included). Both roles of
#: this process count here — a daemon process's numbers are the daemon's,
#: a Spark executor's are its client's (one process, one role in any
#: real deployment; the daemon additionally keeps per-op byte counters).
_TX_BYTES = _metrics.counter(
    "srml_wire_tx_bytes_total", "Frame bytes sent by this process"
)
_RX_BYTES = _metrics.counter(
    "srml_wire_rx_bytes_total", "Frame bytes received by this process"
)

#: Frozen wire-protocol version. Bump ONLY on breaking changes to
#: existing ops' frames or semantics; new ops are additive under the
#: same version.
PROTOCOL_VERSION = 1

MAX_FRAME = 1 << 31  # 2 GB — one Spark partition's batch comfortably fits

#: Frames up to this size send prefix+payload as ONE buffer (one
#: syscall, one TCP segment chain); larger frames skip the concat copy.
_SEND_COALESCE_MAX = 1 << 20

_LEN = struct.Struct(">I")


class ProtocolError(RuntimeError):
    pass


class NoCachedPass(RuntimeError):
    """`rescan` (additive op, docs/protocol.md) asked a job that cannot
    answer for exactly the rows it committed: it keeps no cached pass
    (cache off, over its budget, restored from a snapshot), or the pass
    that fills it is still open. The daemon raises it in the job and
    answers ``{"ok": false, "no_cached_pass": true, "error": …}``; the
    client raises it from that ack. Not a ProtocolError and not an
    OSError: the connection is sound and a replay cannot help — the
    driver re-feeds the pass."""


class FrameTooLarge(ProtocolError):
    """Sender-side MAX_FRAME rejection: deterministic (the payload will
    never fit), so retry loops must surface it instead of replaying."""


def send_frame(sock, payload: bytes) -> None:
    if len(payload) > MAX_FRAME:
        # fail fast sender-side instead of shipping GBs the peer will reject
        raise FrameTooLarge(
            f"frame of {len(payload)} bytes exceeds MAX_FRAME {MAX_FRAME}; "
            "split the batch"
        )
    faults.checkpoint("wire.send_frame")
    cut = faults.truncation("wire.send_frame", len(payload))
    if cut is not None:
        # Chaos path: promise the full frame, deliver a prefix, die — the
        # peer sees exactly what a mid-frame process death produces.
        sock.sendall(_LEN.pack(len(payload)))
        sock.sendall(payload[:cut])
        try:
            sock.close()
        except OSError:
            pass
        raise faults.InjectedDrop(
            f"injected fault: frame truncated at {cut}/{len(payload)} bytes"
        )
    if len(payload) <= _SEND_COALESCE_MAX:
        # One sendall for prefix + payload: the byte stream is identical
        # (the frozen goldens replay unchanged) but the 4-byte prefix no
        # longer goes out as its own syscall — and, under TCP_NODELAY,
        # as its own wire segment. At fleet request rates the header
        # segments were half the packet count of the whole serving path.
        sock.sendall(_LEN.pack(len(payload)) + payload)
    else:
        # Huge frames (multi-MB feeds): skip the concatenation copy —
        # two sendalls are noise next to the payload itself.
        sock.sendall(_LEN.pack(len(payload)))
        sock.sendall(payload)
    _TX_BYTES.inc(_LEN.size + len(payload))


def recv_exact(sock, n: int) -> Optional[bytes]:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(n - len(buf), 1 << 20))
        if not chunk:
            return None  # peer closed
        buf.extend(chunk)
    return bytes(buf)


def recv_frame(sock) -> Optional[bytes]:
    header = recv_exact(sock, _LEN.size)
    if header is None:
        return None
    (n,) = _LEN.unpack(header)
    if n > MAX_FRAME:
        raise ProtocolError(f"frame of {n} bytes exceeds MAX_FRAME {MAX_FRAME}")
    payload = recv_exact(sock, n)
    if payload is not None:
        _RX_BYTES.inc(_LEN.size + n)
    return payload


def send_json(sock, obj: Dict[str, Any]) -> None:
    send_frame(sock, json.dumps(obj).encode())


def recv_json(sock) -> Optional[Dict[str, Any]]:
    frame = recv_frame(sock)
    if frame is None:
        return None
    try:
        obj = json.loads(frame)
    except ValueError as e:
        raise ProtocolError(f"bad JSON frame: {e}") from e
    if not isinstance(obj, dict):
        raise ProtocolError(f"expected JSON object, got {type(obj).__name__}")
    return obj


def send_arrays(sock, arrays: Dict[str, np.ndarray], meta: Dict[str, Any]) -> None:
    """JSON header (meta + array specs) then one raw frame per array."""
    spec = [
        {"name": k, "dtype": str(v.dtype), "shape": list(v.shape)}
        for k, v in arrays.items()
    ]
    send_json(sock, {**meta, "arrays": spec})
    for v in arrays.values():
        send_frame(sock, np.ascontiguousarray(v).tobytes())


def recv_arrays(sock, header: Dict[str, Any]) -> Dict[str, np.ndarray]:
    out = {}
    for spec in header.get("arrays", []):
        frame = recv_frame(sock)
        if frame is None:
            raise ProtocolError("connection closed mid-array")
        arr = np.frombuffer(frame, dtype=np.dtype(spec["dtype"]))
        # frombuffer over the received bytes is read-only; callers own the
        # result (model coefficients) and may mutate — copy.
        out[spec["name"]] = arr.reshape(spec["shape"]).copy()
    return out
