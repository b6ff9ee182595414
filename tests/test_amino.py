"""Amino codec wire-format tests.

The zero-time vector is taken from the reference's pinned amino output
(types/vote_test.go:62: the timestamp field of an empty CanonicalVote) —
it proves seconds use two's-complement uvarint, not zigzag.
"""

import pytest

from txflow_tpu.codec import amino


def test_uvarint_roundtrip():
    for n in [0, 1, 127, 128, 300, 2**32, 2**63, 2**64 - 1]:
        enc = amino.uvarint(n)
        r = amino.AminoReader(enc)
        assert r.read_uvarint() == n
        assert r.eof()


def test_varint_twos_complement():
    # -62135596800 (the Go zero-time unix seconds) must encode as the
    # 10-byte uvarint from the reference vector.
    want = bytes([0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1])
    assert amino.varint(-62135596800) == want
    r = amino.AminoReader(want)
    assert r.read_varint() == -62135596800


def test_zero_time_body_matches_reference_vector():
    # types/vote_test.go:62: field 5 (timestamp) body of zero CanonicalVote is
    # 0xb bytes: 0x8 (field 1 varint) + 10-byte seconds; nanos elided.
    zero_time_unix_ns = -62135596800 * 1_000_000_000
    body = amino.encode_time_body(zero_time_unix_ns)
    assert body == bytes(
        [0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1]
    )
    assert amino.decode_time_body(body) == zero_time_unix_ns


def test_time_body_with_nanos():
    # 2017-12-25T03:00:01.234Z = 1514170801 s + 234ms
    ns = 1514170801 * 1_000_000_000 + 234_000_000
    body = amino.encode_time_body(ns)
    r = amino.AminoReader(body)
    fnum, typ3 = r.read_field_key()
    assert (fnum, typ3) == (1, amino.TYP3_VARINT)
    assert r.read_varint() == 1514170801
    fnum, typ3 = r.read_field_key()
    assert (fnum, typ3) == (2, amino.TYP3_VARINT)
    assert r.read_uvarint() == 234_000_000
    assert r.eof()
    assert amino.decode_time_body(body) == ns


def test_fixed64():
    assert amino.fixed64(1) == bytes([1, 0, 0, 0, 0, 0, 0, 0])
    r = amino.AminoReader(amino.fixed64(-5))
    assert r.read_fixed64() == -5


def test_field_key():
    # (5 << 3) | 2 = 0x2a — the timestamp field tag in the reference vectors.
    assert amino.field_key(5, amino.TYP3_BYTELEN) == bytes([0x2A])
    assert amino.field_key(2, amino.TYP3_8BYTE) == bytes([0x11])


def test_uvarint_overflow_rejected():
    import pytest

    # 11-byte varint and 10-byte with final byte > 1 overflow 64 bits.
    r = amino.AminoReader(bytes([0x80] * 10 + [0x02]))
    with pytest.raises(ValueError):
        r.read_uvarint()
    r = amino.AminoReader(bytes([0xFF] * 9 + [0x02]))
    with pytest.raises(ValueError):
        r.read_uvarint()
    # Max uint64 still decodes.
    r = amino.AminoReader(amino.uvarint(2**64 - 1))
    assert r.read_uvarint() == 2**64 - 1


def _time_body_by_the_rule(unix_ns: int) -> bytes:
    """The amino rule spelled out with the plain primitives: seconds and
    nanos as ``field_key + varint``, each elided when zero."""
    seconds, nanos = divmod(unix_ns, 1_000_000_000)
    out = b""
    if seconds:
        out += amino.field_key(1, amino.TYP3_VARINT) + amino.varint(seconds)
    if nanos:
        out += amino.field_key(2, amino.TYP3_VARINT) + amino.uvarint(nanos)
    return out


@pytest.mark.parametrize("unix_ns", [
    0, 1, -1, 127, 128, 16_383, 16_384, 2**21 - 1, 2**21, 2**28 - 1, 2**28,
    999_999_999, 1_000_000_000, 1_000_000_001, 1_999_999_999,
    1_700_000_000_000_000_000, 1_700_000_000_123_456_789,
    1_700_000_000_999_999_999, 1_700_000_000_000_016_384,
    -62135596800 * 1_000_000_000, -62135596800 * 1_000_000_000 + 5,
    2**62, -(2**62), 2**63 - 1,
])
def test_time_body_is_field_key_plus_varint(unix_ns):
    # encode_time_body reads its varints from tables and memoizes the
    # seconds' field (PR 34): the bytes are the rule's, for every value
    body = amino.encode_time_body(unix_ns)
    assert body == _time_body_by_the_rule(unix_ns)
    assert amino.encode_time_body(unix_ns) == body  # from the memo now
    assert amino.decode_time_body(body) == unix_ns


def test_time_body_random_and_memo_turnover():
    import random

    rng = random.Random(34)
    for _ in range(20_000):
        ns = rng.randrange(-(2**63), 2**63)
        assert amino.encode_time_body(ns) == _time_body_by_the_rule(ns)
    # more distinct seconds than the memo holds: it is dropped and refilled
    base = 1_700_000_000
    for s in range(3 * amino._SECONDS_MEMO):
        ns = (base + s) * 1_000_000_000 + s
        assert amino.encode_time_body(ns) == _time_body_by_the_rule(ns)
    assert len(amino._seconds_field) <= amino._SECONDS_MEMO
