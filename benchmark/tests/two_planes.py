"""A profile with two device planes, made from the recorded one-chip
trace: the second plane is the first under the name `/device:TPU:1` with
the later half of the events of its `XLA Ops` line left out, so that it
is the less busy one. Works on the file's protobuf wire format (XSpace:
planes = 1; XPlane: name = 2, lines = 3; XLine: name = 2, events = 4),
with nothing but the standard library."""


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _encode(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def fields(buf):
    """[(field number, wire type, payload)] of one message; the payload
    of a varint field is its value, of a length-delimited one its bytes."""
    out, i = [], 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            payload, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            payload, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            payload, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        out.append((number, wire, payload))
    return out


def message(parts):
    out = bytearray()
    for number, wire, payload in parts:
        out += _encode(number << 3 | wire)
        if wire == 0:
            out += _encode(payload)
        elif wire == 2:
            out += _encode(len(payload)) + payload
        else:
            out += payload
    return bytes(out)


def _half_the_ops(line):
    parts = fields(line)
    if (2, 2, b"XLA Ops") not in parts:
        return line
    events = [p for p in parts if p[0] == 4]
    dropped = {id(p) for p in events[len(events) // 2:]}
    return message([p for p in parts if id(p) not in dropped])


def with_second_device(space: bytes, first=b"/device:TPU:0",
                       second=b"/device:TPU:1") -> bytes:
    parts = fields(space)
    plane = next(p for n, w, p in parts
                 if n == 1 and (2, 2, first) in fields(p))
    twin = message([
        (n, w, second if (n, p) == (2, first) else
         _half_the_ops(p) if n == 3 else p) for n, w, p in fields(plane)])
    return message(parts + [(1, 2, twin)])
