"""What ``xplane_writer`` lacks for the program's side of a trace:
events that carry stats, of their own (a ``TraceAnnotation``'s keyword
arguments) or on their metadata (an op's ``op_name``).

    XEvent{4: XStat*}  XEventMetadata{5: XStat*}
    XPlane{5: map<int64, XStatMetadata>}  XStatMetadata{1: id, 2: name}
    XStat{1: metadata_id, 4: int64_value | 5: str_value}
"""

from __future__ import annotations

from chipbench.tests.xplane_writer import _bytes, _int


def xspace(planes) -> bytes:
    """``planes``: ``[(plane_name, [(line_name, [event, ...]), ...]),
    ...]``, an event ``(name, start_ns, duration_ns)`` or ``(name,
    start_ns, duration_ns, own_stats, metadata_stats)`` with each of
    the two a dict of ``int`` or ``str`` values (or ``None``)."""
    out = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        meta, meta_stats, stat_ids = {}, {}, {}

        def stats(field, d):
            return b"".join(_bytes(field, _stat(
                stat_ids.setdefault(k, len(stat_ids) + 1), v))
                for k, v in (d or {}).items())

        body = _int(1, pid) + _bytes(2, pname.encode())
        for lid, (lname, events) in enumerate(lines, 1):
            t0 = min((int(ev[1]) for ev in events), default=0)
            lb = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, t0)
            for name, start, dur, *rest in events:
                own, on_meta = (rest + [None, None])[:2]
                mid = meta.setdefault(name, len(meta) + 1)
                if on_meta:
                    meta_stats[mid] = on_meta
                lb += _bytes(4, _int(1, mid)
                             + _int(2, (int(start) - t0) * 1000)
                             + _int(3, int(dur) * 1000) + stats(4, own))
            body += _bytes(3, lb)
        for name, mid in meta.items():
            body += _bytes(4, _int(1, mid) + _bytes(
                2, _int(1, mid) + _bytes(2, name.encode())
                + stats(5, meta_stats.get(mid))))
        for k, sid in stat_ids.items():
            body += _bytes(5, _int(1, sid) + _bytes(
                2, _int(1, sid) + _bytes(2, k.encode())))
        out += _bytes(1, body)
    return out


def _stat(sid: int, v) -> bytes:
    return _int(1, sid) + (_bytes(5, v.encode()) if isinstance(v, str)
                           else _int(4, v))
