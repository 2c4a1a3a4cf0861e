"""The least MQTT 3.1.1 a load generator needs, on raw bytes.

The benchmark speaks the wire protocol itself, so the program's codec is
inside what is measured and compared, not part of the yardstick. Encoders
return bytes; ``Parser.feed`` returns ``(type, flags, body)`` tuples.
"""

from __future__ import annotations

CONNACK, PUBLISH, PUBACK, SUBACK = 2, 3, 4, 9


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, b = n >> 7, n & 0x7F
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _packet(first: int, body: bytes) -> bytes:
    return bytes((first,)) + _varint(len(body)) + body


def _str(s: str) -> bytes:
    b = s.encode()
    return len(b).to_bytes(2, "big") + b


def connect(client_id: str) -> bytes:
    # clean session, keepalive 0 (no pings: the run is shorter than any timeout)
    return _packet(0x10, b"\x00\x04MQTT\x04\x02\x00\x00" + _str(client_id))


def subscribe(packet_id: int, filters, qos: int) -> bytes:
    q = bytes((qos,))
    return _packet(0x82, packet_id.to_bytes(2, "big")
                   + b"".join(_str(f) + q for f in filters))


def publish(topic: str, payload: bytes, qos: int, packet_id: int = 0) -> bytes:
    head = _str(topic) + (packet_id.to_bytes(2, "big") if qos else b"")
    return _packet(0x30 | (qos << 1), head + payload)


def puback(packet_id: int) -> bytes:
    return b"\x40\x02" + packet_id.to_bytes(2, "big")


DISCONNECT = b"\xe0\x00"


def publish_fields(flags: int, body):
    """→ (payload, qos, packet_id) of a PUBLISH body (the topic is skipped:
    the publish id rides the payload)."""
    qos = (flags >> 1) & 3
    at = 2 + ((body[0] << 8) | body[1])
    if qos:
        return body[at + 2:], qos, (body[at] << 8) | body[at + 1]
    return body[at:], 0, 0


class Parser:
    """Incremental splitter of a byte stream into MQTT control packets."""

    __slots__ = ("buf",)

    def __init__(self) -> None:
        self.buf = b""

    def feed(self, data: bytes):
        buf = self.buf + data if self.buf else data
        out = []
        i, n = 0, len(buf)
        while n - i >= 2:
            first = buf[i]
            rl, shift, j = 0, 0, i + 1
            while True:
                if j >= n:
                    self.buf = buf[i:]
                    return out
                b = buf[j]
                j += 1
                rl |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
                if shift > 21:
                    raise ValueError("malformed remaining length")
            if n - j < rl:
                break
            out.append((first >> 4, first & 0x0F, buf[j:j + rl]))
            i = j + rl
        self.buf = buf[i:]
        return out
