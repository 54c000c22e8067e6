"""Write a small ``.xplane.pb`` by hand (protobuf wire format, no library):
enough of tsl's ``XSpace`` for ``jax.profiler.ProfileData`` to read planes,
lines and events with a name, a start and a duration. Tests use it to lay out
device timelines whose busy time, gaps and overlaps can be worked out by
hand.

    XSpace  { repeated XPlane planes = 1 }
    XPlane  { id = 1; name = 2; repeated XLine lines = 3;
              map<int64, XEventMetadata> event_metadata = 4 }
    XLine   { id = 1; name = 2; timestamp_ns = 3; repeated XEvent events = 4 }
    XEvent  { metadata_id = 1; offset_ps = 2; duration_ps = 3 }
    XEventMetadata { id = 1; name = 2 }
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint((field << 3) | 2) + _varint(len(payload)) + payload


def plane(pid: int, name: str,
          lines: Dict[str, List[Tuple[str, int, int]]]) -> bytes:
    """``lines``: line name -> [(event name, start_ns, duration_ns)]."""
    meta: Dict[str, int] = {}
    body = _int(1, pid) + _bytes(2, name.encode())
    for lid, (lname, events) in enumerate(lines.items(), 1):
        lb = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, 0)
        for ename, start_ns, dur_ns in events:
            mid = meta.setdefault(ename, len(meta) + 1)
            lb += _bytes(4, _int(1, mid) + _int(2, start_ns * 1000)
                         + _int(3, dur_ns * 1000))
        body += _bytes(3, lb)
    for ename, mid in meta.items():
        entry = _int(1, mid) + _bytes(
            2, _int(1, mid) + _bytes(2, ename.encode()))
        body += _bytes(4, entry)
    return body


def write(path: str, planes: List[bytes]) -> None:
    with open(path, "wb") as f:
        f.write(b"".join(_bytes(1, p) for p in planes))
