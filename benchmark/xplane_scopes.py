"""Which ``jax.named_scope`` a device operation ran under, from a run's
``*.xplane.pb``.

``jax.profiler.ProfileData`` gives a device event's name (the operation's HLO
text) and its own stats, not the stats of the event's METADATA, where the
profiler keeps the operation's ``op_name`` (``jit(f)/scope/.../primitive``,
what ``jax.named_scope`` prefixes). This module reads just that out of the
file, with a minimal reader of the protobuf wire format (varints and
length-delimited fields; the schema is tsl's ``xplane.proto``: XSpace.planes =
1; XPlane.name = 2, .event_metadata = 4 (a map: key = 1, value = 2);
XEventMetadata.name = 2, .display_name = 4, .stats = 5; XStat.str_value = 5).
"""


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def op_names(path, plane_prefix="/device:TPU:"):
    """{event name: the strings its metadata's stats hold, joined by a
    newline} for the first plane whose name starts with ``plane_prefix``; the
    ``op_name`` with the named scopes is one of the strings. Keys are both
    the metadata's ``name`` (the HLO text ``ProfileData`` shows) and its
    ``display_name``. {} where the file has no such plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name, metadata = "", []
        for f2, w2, v in _fields(plane):
            if f2 == 2 and w2 == 2:
                name = _text(v)
            elif f2 == 4 and w2 == 2:
                metadata.append(v)
        if not name.startswith(plane_prefix):
            continue
        out = {}
        for entry in metadata:
            for f3, w3, meta in _fields(entry):
                if f3 != 2 or w3 != 2:
                    continue
                names, strings = [], []
                for f4, w4, v in _fields(meta):
                    if f4 in (2, 4) and w4 == 2:
                        names.append(_text(v))
                    elif f4 == 5 and w4 == 2:
                        strings += [_text(s) for f5, w5, s in _fields(v)
                                    if f5 == 5 and w5 == 2]
                for key in names:
                    out[key] = "\n".join(strings)
        return out
    return {}
