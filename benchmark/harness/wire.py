"""Envoy RLS v3 wire format, encoded and decoded by hand.

The load generators are jax-free worker processes; they send raw bytes
over a gRPC channel, so they need neither the program's protobuf modules
nor the protobuf runtime. Field numbers are those of
envoy/service/ratelimit/v3/rls.proto and
envoy/extensions/common/ratelimit/v3/ratelimit.proto."""

from __future__ import annotations

RLS_V3_METHOD = "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit"
CODE_UNKNOWN, CODE_OK, CODE_OVER = 0, 1, 2


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(tag: int, payload: bytes) -> bytes:
    return bytes((tag,)) + _varint(len(payload)) + payload


def encode_request(domain: str, descriptors) -> bytes:
    """RateLimitRequest{domain=1, descriptors=2 [entries=1 {key=1, value=2}]}.
    `descriptors` is a list of [(key, value), ...] entry lists."""
    body = _field(0x0A, domain.encode())
    for entries in descriptors:
        desc = b"".join(
            _field(0x0A, _field(0x0A, k.encode()) + _field(0x12, v.encode()))
            for k, v in entries
        )
        body += _field(0x12, desc)
    return body


def _read_varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = n = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _skip(buf: bytes, i: int, wire: int) -> int:
    if wire == 0:
        return _read_varint(buf, i)[1]
    if wire == 2:
        n, i = _read_varint(buf, i)
        return i + n
    if wire == 1:
        return i + 8
    if wire == 5:
        return i + 4
    raise ValueError(f"wire type {wire}")


def decode_status_codes(buf: bytes) -> list[int]:
    """The `code` of each DescriptorStatus (RateLimitResponse field 2), in
    order; a status without the field is UNKNOWN (proto3 default)."""
    codes = []
    i, end = 0, len(buf)
    while i < end:
        key, i = _read_varint(buf, i)
        field, wire = key >> 3, key & 7
        if field == 2 and wire == 2:
            n, i = _read_varint(buf, i)
            j, stop, code = i, i + n, CODE_UNKNOWN
            while j < stop:
                k2, j = _read_varint(buf, j)
                if k2 == 0x08:
                    code, j = _read_varint(buf, j)
                else:
                    j = _skip(buf, j, k2 & 7)
            codes.append(code)
            i = stop
        else:
            i = _skip(buf, i, wire)
    return codes
