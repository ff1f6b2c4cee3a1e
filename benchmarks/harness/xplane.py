"""Read a profiler ``.xplane.pb`` with nothing but the wire format.

``jax.profiler.ProfileData`` gives an event's own stats but not those of its
metadata, and on the TPU the ``named_scope`` path of an operation lives there
(``tf_op`` / ``long_name`` of the XEventMetadata).  The schema is small
(tsl/profiler/protobuf/xplane.proto): only the fields used are decoded.
"""
import struct


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield num, wire, val


def _stat(buf):
    """(stat metadata id, kind, value): kind 'str', 'ref' or 'num'."""
    mid, kind, value = 0, None, None
    for num, wire, val in _fields(buf):
        if num == 1:
            mid = val
        elif num == 5:
            kind, value = "str", bytes(val).decode("utf-8", "replace")
        elif num == 7:
            kind, value = "ref", val
        elif num == 2:
            kind, value = "num", struct.unpack("<d", val)[0]
        elif num in (3, 4):
            kind, value = "num", val
    return mid, kind, value


def _stat_text(stats, stat_names):
    out = []
    for _, kind, value in stats:
        if kind == "str":
            out.append(value)
        elif kind == "ref":
            out.append(stat_names.get(value, ""))
    return " ".join(s for s in out if s)


def read_events(path, keep):
    """Events of every line as dicts {"plane", "line", "name", "meta", "ts",
    "dur"} (nanoseconds); ``keep(plane name, line name, event name)`` says
    which to return."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    events = []
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, lines, ev_meta, stat_names = "", [], {}, {}
        for pn, _, pv in _fields(plane):
            if pn == 2:
                name = bytes(pv).decode()
            elif pn == 3:
                lines.append(pv)
            elif pn == 4:
                for en, _, entry in _fields(pv):
                    if en == 2:
                        md = {"name": "", "display": "", "stats": []}
                        mid = 0
                        for mn, _, mv in _fields(entry):
                            if mn == 1:
                                mid = mv
                            elif mn == 2:
                                md["name"] = bytes(mv).decode("utf-8",
                                                              "replace")
                            elif mn == 4:
                                md["display"] = bytes(mv).decode("utf-8",
                                                                 "replace")
                            elif mn == 5:
                                md["stats"].append(_stat(mv))
                        ev_meta[mid] = md
            elif pn == 5:
                for en, _, entry in _fields(pv):
                    if en == 2:
                        sid, sname = 0, ""
                        for mn, _, mv in _fields(entry):
                            if mn == 1:
                                sid = mv
                            elif mn == 2:
                                sname = bytes(mv).decode("utf-8", "replace")
                        stat_names[sid] = sname
        meta_text = {}
        for line in lines:
            lname, t0, evs = "", 0, []
            for ln, _, lv in _fields(line):
                if ln == 2:
                    lname = bytes(lv).decode()
                elif ln == 3:
                    t0 = lv
                elif ln == 4:
                    evs.append(lv)
            for ev in evs:
                mid = off = dur = 0
                stats = []
                for en, _, evv in _fields(ev):
                    if en == 1:
                        mid = evv
                    elif en == 2:
                        off = evv
                    elif en == 3:
                        dur = evv
                    elif en == 4:
                        stats.append(evv)
                md = ev_meta.get(mid, {"name": "", "display": "",
                                       "stats": []})
                if not keep(name, lname, md["name"]):
                    continue
                if mid not in meta_text:
                    meta_text[mid] = _stat_text(md["stats"], stat_names)
                own = _stat_text([_stat(s) for s in stats], stat_names)
                events.append({
                    "plane": name, "line": lname,
                    # a device op's name is its whole HLO text; its display
                    # name is short.  A host span's display name drops the
                    # part before the colon, so keep its full name.
                    "name": (md["display"] or md["name"])
                    if name.startswith("/device:") else md["name"],
                    "meta": " ".join(s for s in (md["name"], meta_text[mid],
                                                 own) if s),
                    "ts": t0 + off / 1e3, "dur": dur / 1e3})
    return events
