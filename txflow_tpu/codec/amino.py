"""Minimal amino binary codec — the subset used by TxVote sign bytes and wire.

go-txflow canonicalizes votes with go-amino v0.14 ``MarshalBinaryLengthPrefixed``
(reference: types/tx_vote.go:83-89, types/codec.go:9-18). Commit decisions hinge
on bit-exact sign bytes, so this module reproduces the relevant wire rules:

- unsigned varints (LEB128);
- signed varints as two's-complement uvarint (proto3 ``int64`` style — the
  reference vectors in types/vote_test.go:62 encode the zero-time seconds
  -62135596800 as a 10-byte uvarint, proving amino does NOT zigzag here);
- field keys ``(field_number << 3) | typ3`` with typ3 Varint=0 / 8Byte=1 /
  ByteLength=2;
- ``binary:"fixed64"`` int64 as 8-byte little-endian (typ3 8Byte);
- ``time.Time`` as an embedded struct {1: seconds varint, 2: nanos varint},
  each elided when zero;
- zero-value field elision: ints == 0, empty strings/slices are skipped;
  fixed-size byte arrays are ALWAYS written (amino's isDefaultValue does not
  treat arrays as default — hence CanonicalTxVote.TxKey serializes as 32 zero
  bytes); struct fields are skipped only when their encoded body is empty
  (the vectors show an empty CanonicalBlockID elided but a zero time written).
"""

from __future__ import annotations

TYP3_VARINT = 0
TYP3_8BYTE = 1
TYP3_BYTELEN = 2

_U64_MASK = (1 << 64) - 1


def uvarint(n: int) -> bytes:
    """LEB128 unsigned varint."""
    if 0 <= n < 0x80:
        return _SMALL[n]  # the overwhelmingly common case on this wire
    if n < 0:
        raise ValueError("uvarint of negative value")
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


_SMALL = [bytes((i,)) for i in range(0x80)]


def varint(n: int) -> bytes:
    """Signed varint, two's-complement-as-uint64 (proto3 int64 semantics)."""
    return uvarint(n & _U64_MASK)


def field_key(field_num: int, typ3: int) -> bytes:
    return uvarint((field_num << 3) | typ3)


def fixed64(n: int) -> bytes:
    return (n & _U64_MASK).to_bytes(8, "little")


def length_prefixed(payload: bytes) -> bytes:
    return uvarint(len(payload)) + payload


# A varint in groups of 14 bits, two bytes a group: _VAR14_MORE[x] is x
# with both continuation bits set (more groups follow), _VAR14_LAST[x] the
# minimal varint of x (the last group). A timestamp's nanoseconds are 30
# bits: two lookups and a byte where the 7-bit loop runs five rounds.
_VAR14_MORE = [bytes(((x & 0x7F) | 0x80, (x >> 7) | 0x80)) for x in range(1 << 14)]
_VAR14_LAST = [uvarint(x) for x in range(1 << 14)]

# seconds -> their field (key 0x08 + varint), the votes of a frame share a
# handful of seconds; dropped whole when it has grown past _SECONDS_MEMO
_seconds_field: dict[int, bytes] = {}
_SECONDS_MEMO = 512


def encode_time_body(unix_ns: int) -> bytes:
    """Body of an amino-embedded time.Time given integer unix nanoseconds.

    seconds = floor(unix_ns / 1e9) (matches Go Time.Unix() for negative
    times), nanos in [0, 1e9). Each field elided when zero. Runs once a
    vote on the encode and sign-bytes paths, hence the memo and the
    tables (field keys 0x08/0x10 = (fnum << 3) | TYP3_VARINT); the bytes
    are ``field_key + varint`` of each, pinned against exactly that by
    tests/test_amino.py.
    """
    seconds, nanos = divmod(unix_ns, 1_000_000_000)
    out = _seconds_field.get(seconds)
    if out is None:
        if len(_seconds_field) >= _SECONDS_MEMO:
            _seconds_field.clear()
        out = _seconds_field[seconds] = b"\x08" + varint(seconds) if seconds else b""
    if nanos >= 0x10000000:
        return (
            out + b"\x10" + _VAR14_MORE[nanos & 0x3FFF]
            + _VAR14_MORE[(nanos >> 14) & 0x3FFF] + _SMALL[nanos >> 28]
        )
    if nanos >= 0x4000:
        return out + b"\x10" + _VAR14_MORE[nanos & 0x3FFF] + _VAR14_LAST[nanos >> 14]
    if nanos:
        return out + b"\x10" + _VAR14_LAST[nanos]
    return out


class AminoReader:
    """Cursor over amino binary bytes for decoding."""

    def __init__(self, data: bytes, pos: int = 0, end: int | None = None):
        self.data = data
        self.pos = pos
        self.end = len(data) if end is None else end

    def eof(self) -> bool:
        return self.pos >= self.end

    def read_uvarint(self) -> int:
        # Matches Go binary.Uvarint overflow rules: at most 10 bytes, and the
        # 10th byte may only be 0x01 (values must fit in 64 bits).
        n = 0
        shift = 0
        while True:
            if self.pos >= self.end:
                raise ValueError("truncated uvarint")
            b = self.data[self.pos]
            self.pos += 1
            if shift == 63 and b > 1:
                raise ValueError("uvarint overflows 64 bits")
            n |= (b & 0x7F) << shift
            if not b & 0x80:
                return n
            shift += 7
            if shift > 63:
                raise ValueError("uvarint overflows 64 bits")

    def read_varint(self) -> int:
        n = self.read_uvarint() & _U64_MASK
        if n >= 1 << 63:
            n -= 1 << 64
        return n

    def read_field_key(self) -> tuple[int, int]:
        k = self.read_uvarint()
        return k >> 3, k & 0x07

    def read_fixed64(self) -> int:
        if self.pos + 8 > self.end:
            raise ValueError("truncated fixed64")
        n = int.from_bytes(self.data[self.pos : self.pos + 8], "little")
        self.pos += 8
        if n >= 1 << 63:
            n -= 1 << 64
        return n

    def read_bytes(self) -> bytes:
        ln = self.read_uvarint()
        if self.pos + ln > self.end:
            raise ValueError("truncated byte field")
        out = self.data[self.pos : self.pos + ln]
        self.pos += ln
        return out

    def sub_reader(self) -> "AminoReader":
        ln = self.read_uvarint()
        if self.pos + ln > self.end:
            raise ValueError("truncated embedded struct")
        r = AminoReader(self.data, self.pos, self.pos + ln)
        self.pos += ln
        return r

    def skip_field(self, typ3: int) -> None:
        if typ3 == TYP3_VARINT:
            self.read_uvarint()
        elif typ3 == TYP3_8BYTE:
            self.read_fixed64()
        elif typ3 == TYP3_BYTELEN:
            self.read_bytes()
        else:
            raise ValueError(f"unknown typ3 {typ3}")


def read_uvarint(data: bytes, pos: int = 0) -> tuple[int, int]:
    r = AminoReader(data, pos)
    n = r.read_uvarint()
    return n, r.pos


def decode_time_body(body: bytes) -> int:
    """Inverse of encode_time_body -> unix nanoseconds."""
    r = AminoReader(body)
    seconds = 0
    nanos = 0
    while not r.eof():
        fnum, typ3 = r.read_field_key()
        if fnum == 1 and typ3 == TYP3_VARINT:
            seconds = r.read_varint()
        elif fnum == 2 and typ3 == TYP3_VARINT:
            nanos = r.read_uvarint()
        else:
            r.skip_field(typ3)
    return seconds * 1_000_000_000 + nanos
