"""A minimal writer of the profiler's ``XSpace`` protobuf (the
``.xplane.pb`` format), enough to build small fixture traces that
``jax.profiler.ProfileData.from_file`` reads back. Wire format by hand:
no protobuf package, no TensorFlow.

    XSpace{1: XPlane*}  XPlane{1: id, 2: name, 3: XLine*,
    4: map<int64, XEventMetadata>}  XLine{1: id, 2: name,
    3: timestamp_ns, 4: XEvent*}  XEvent{1: metadata_id, 2: offset_ps,
    3: duration_ps}  XEventMetadata{1: id, 2: name}
"""

from __future__ import annotations


def _varint(n: int) -> bytes:
    out = bytearray()
    n &= (1 << 64) - 1
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(int(v))


def _bytes(field: int, b: bytes) -> bytes:
    return _varint((field << 3) | 2) + _varint(len(b)) + b


def xspace(planes) -> bytes:
    """``planes``: ``[(plane_name, [(line_name, [(event_name, start_ns,
    duration_ns), ...]), ...]), ...]``."""
    out = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        meta = {}
        body = _int(1, pid) + _bytes(2, pname.encode())
        for lid, (lname, events) in enumerate(lines, 1):
            t0 = min((int(s) for _, s, _ in events), default=0)
            lb = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, t0)
            for name, start, dur in events:
                mid = meta.setdefault(name, len(meta) + 1)
                lb += _bytes(4, _int(1, mid)
                             + _int(2, (int(start) - t0) * 1000)
                             + _int(3, int(dur) * 1000))
            body += _bytes(3, lb)
        for name, mid in meta.items():
            entry = _int(1, mid) + _bytes(
                2, _int(1, mid) + _bytes(2, name.encode()))
            body += _bytes(4, entry)
        out += _bytes(1, body)
    return out


def trim(path: str, out_path: str, per_line: int = 400) -> None:
    """Cut the trace at ``path`` down to a fixture: from the start of
    the benchmark's ``cb.slice`` span, the time range of the first
    ``per_line`` events of the device's op line; every device line and
    the host's ``cb.`` spans are kept inside that range (a span that
    runs past its end, the slice marker among them, is cut to it). How
    ``fixture_k1.xplane.pb`` was made from a chip run of
    ``flagship_n4096.solve_k1 --trace 1``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    triple = lambda e: (e.name, int(e.start_ns), int(e.duration_ns))
    lo = min((int(e.start_ns) for p in data.planes
              if p.name.startswith("/host:CPU") for l in p.lines
              for e in l.events if e.name == "cb.slice"), default=0)
    hi = 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ev = sorted((t for t in map(triple, line.events)
                                 if t[1] >= lo),
                                key=lambda t: t[1])[:per_line]
                    hi = max([hi] + [s + d for _, s, d in ev])
    planes = []
    for plane in data.planes:
        dev = plane.name.startswith("/device:")
        if not dev and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            ev = [(n, s, min(d, hi - s)) for n, s, d in
                  map(triple, line.events) if lo <= s < hi
                  and (dev or n.startswith("cb."))
                  and (not dev or s + d <= hi or n.startswith("%while"))]
            if ev:
                lines.append((line.name, ev))
        planes.append((plane.name, lines))
    with open(out_path, "wb") as f:
        f.write(xspace(planes))
