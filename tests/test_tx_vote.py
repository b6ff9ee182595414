"""TxVote sign bytes + wire encoding tests (mirrors reference types/vote_test.go,
with vectors regenerated for the actual CanonicalTxVote shape — the reference's
own vectors are stale copies of upstream Vote vectors, per SURVEY.md section 0).

Vector provenance: the pinned byte strings below were generated by THIS
codebase's encoder and audited by hand against the amino binary spec
(field keys = (fnum << 3) | typ3, LEB128 varints, proto3 time embedding)
— NOT by running go-amino, which would be the gold standard. This build
environment has no Go toolchain (go-amino cannot be executed here; the
reference vendors no generated vectors that match CanonicalTxVote, see
SURVEY §0). Mitigations in place: the fields covered include varint/
fixed64 edge heights and times with/without nanos; the decoder is a
second, independently-written parser that must round-trip every vector;
and the native C module independently reimplements SHA-512 + mod-L from
their specs (tests/test_native_prep.py). If a Go toolchain ever becomes
available, regenerate these with amino.MarshalBinaryLengthPrefixed over
CanonicalTxVote and diff."""

import time

import pytest

from txflow_tpu.codec import amino
from txflow_tpu.crypto import ed25519
from txflow_tpu.crypto.hash import address_hash, tx_hash, tx_key
from txflow_tpu.types import (
    MAX_VOTE_BYTES,
    MockPV,
    TxVote,
    canonical_sign_bytes,
    decode_tx_vote,
    encode_tx_vote,
)

# 2017-12-25T03:00:01.234Z, the reference's example timestamp.
STAMP_NS = 1514170801 * 1_000_000_000 + 234_000_000


def example_vote() -> TxVote:
    return TxVote(
        height=12345,
        tx_hash=tx_hash(b"tx_hash"),
        tx_key=tx_key(b"tx_hash"),
        timestamp_ns=STAMP_NS,
        validator_address=address_hash(b"validator_address"),
    )


def test_sign_bytes_structure():
    vote = example_vote()
    sb = vote.sign_bytes("test_chain_id")
    # Length-prefixed.
    total, pos = amino.read_uvarint(sb)
    assert pos + total == len(sb)
    r = amino.AminoReader(sb, pos)
    # Field 1: height fixed64.
    fnum, typ3 = r.read_field_key()
    assert (fnum, typ3) == (1, amino.TYP3_8BYTE)
    assert r.read_fixed64() == 12345
    # Field 2: tx hash string (64 hex chars).
    fnum, typ3 = r.read_field_key()
    assert (fnum, typ3) == (2, amino.TYP3_BYTELEN)
    assert r.read_bytes().decode() == tx_hash(b"tx_hash")
    # Field 3: TxKey — ALWAYS 32 zero bytes (canonicalization drops the key).
    fnum, typ3 = r.read_field_key()
    assert (fnum, typ3) == (3, amino.TYP3_BYTELEN)
    assert r.read_bytes() == bytes(32)
    # Field 4: timestamp.
    fnum, typ3 = r.read_field_key()
    assert (fnum, typ3) == (4, amino.TYP3_BYTELEN)
    assert amino.decode_time_body(r.read_bytes()) == STAMP_NS
    # Field 5: chain id.
    fnum, typ3 = r.read_field_key()
    assert (fnum, typ3) == (5, amino.TYP3_BYTELEN)
    assert r.read_bytes() == b"test_chain_id"
    assert r.eof()


def test_sign_bytes_empty_vote():
    # Height 0 and empty tx hash elided; TxKey + timestamp present.
    sb = canonical_sign_bytes("", 0, "", STAMP_NS)
    total, pos = amino.read_uvarint(sb)
    r = amino.AminoReader(sb, pos)
    fnum, typ3 = r.read_field_key()
    assert fnum == 3  # first non-elided field is TxKey
    r.read_bytes()
    fnum, _ = r.read_field_key()
    assert fnum == 4
    r.read_bytes()
    assert r.eof()


def test_sign_bytes_pinned_vector():
    # Pinned regression vector: any change to the canonical encoding breaks
    # every signature in the network.
    #
    # PROVENANCE: these bytes are hand-derived from the amino wire spec
    # (field keys = fnum<<3|typ3; fixed64 little-endian; protobuf-style
    # Timestamp body) and cross-checked field by field in
    # test_amino.py — NOT generated by the Go amino encoder. This build
    # image ships no Go toolchain (`which go` is empty; zero network
    # egress), so go-amino ground-truth vectors cannot be produced here;
    # the reference's own vectors are unusable (stale upstream Vote
    # copies with fields CanonicalTxVote does not have, SURVEY §0).
    # Generating vectors with real go-amino remains the right follow-up
    # wherever a Go toolchain exists.
    sb = canonical_sign_bytes("test_chain", 1, "AB", 1_000_000_000)
    want = bytes(
        [0x3F]  # total length 63: 9 (height) + 4 (hash) + 34 (key) + 4 (ts) + 12 (chain)
        + [0x09] + [1, 0, 0, 0, 0, 0, 0, 0]  # height fixed64 = 1
        + [0x12, 0x02] + list(b"AB")  # tx hash
        + [0x1A, 0x20] + [0] * 32  # zero TxKey
        + [0x22, 0x02, 0x08, 0x01]  # timestamp {seconds: 1}
        + [0x2A, 0x0A] + list(b"test_chain")
    )
    assert sb == want


def test_sign_and_verify():
    pv = MockPV()
    vote = example_vote()
    vote.validator_address = pv.get_address()
    pv.sign_tx_vote("test_chain_id", vote)
    assert vote.verify("test_chain_id", pv.get_pub_key()) is None
    # Wrong chain id fails.
    assert vote.verify("other_chain", pv.get_pub_key()) is not None
    # Wrong pubkey fails on address check.
    other = MockPV()
    assert vote.verify("test_chain_id", other.get_pub_key()) == (
        "invalid validator address"
    )


def test_broken_signer_rejected():
    pv = MockPV(break_tx_vote_signing=True)
    vote = example_vote()
    vote.validator_address = pv.get_address()
    pv.sign_tx_vote("test_chain_id", vote)
    assert vote.verify("test_chain_id", pv.get_pub_key()) == "invalid signature"


def test_wire_roundtrip():
    pv = MockPV()
    vote = example_vote()
    vote.validator_address = pv.get_address()
    pv.sign_tx_vote("test_chain_id", vote)
    enc = encode_tx_vote(vote)
    dec = decode_tx_vote(enc)
    assert dec == vote
    assert vote.size() == len(enc)


def test_max_vote_bytes():
    # A fully-populated vote must fit in the reference's 223-byte cap.
    pv = MockPV()
    vote = example_vote()
    vote.validator_address = pv.get_address()
    pv.sign_tx_vote("test_chain_id", vote)
    assert vote.size() <= MAX_VOTE_BYTES


def test_validate_basic():
    pv = MockPV()
    vote = example_vote()
    vote.validator_address = pv.get_address()
    pv.sign_tx_vote("test_chain_id", vote)
    assert vote.validate_basic() is None
    bad = vote.copy()
    bad.height = -1
    assert bad.validate_basic() is not None
    bad = vote.copy()
    bad.validator_address = b"\x00"
    assert bad.validate_basic() is not None
    bad = vote.copy()
    bad.signature = None
    assert bad.validate_basic() is not None
    bad = vote.copy()
    bad.signature = bytes(65)
    assert bad.validate_basic() is not None


def test_timestamp_now_default():
    before = time.time_ns()
    vote = TxVote(1, "AB", bytes(32))
    assert before <= vote.timestamp_ns <= time.time_ns()


def test_decode_caches_canonical_wire_bytes():
    """decode(encode(v)) must serve encode from the cached input bytes, and
    non-canonical peer encodings must NOT be cached (re-encode canonicalizes)."""
    import hashlib

    from txflow_tpu.codec import amino
    from txflow_tpu.types.tx_vote import decode_tx_vote, encode_tx_vote

    tx_key = hashlib.sha256(b"t").digest()
    v = TxVote(
        height=7,
        tx_hash=tx_key.hex().upper(),
        tx_key=tx_key,
        timestamp_ns=1_700_000_000_123_456_789,
        validator_address=b"\x01" * 20,
        signature=b"\x02" * 64,
    )
    wire = encode_tx_vote(v)
    d = decode_tx_vote(wire)
    assert d._wire_cache == wire
    assert encode_tx_vote(d) == wire

    # unknown trailing field -> decoded fine, but cache must be refused and
    # the re-encode must canonicalize (drop the unknown field)
    tampered = wire + bytes(amino.field_key(9, amino.TYP3_BYTELEN)) + bytes(
        amino.length_prefixed(b"x")
    )
    d2 = decode_tx_vote(tampered)
    assert d2._wire_cache is None
    assert encode_tx_vote(d2) == wire

    # explicit default (height=0) -> non-canonical, not cached
    v0 = TxVote(
        height=0,
        tx_hash=tx_key.hex().upper(),
        tx_key=tx_key,
        timestamp_ns=1,
        validator_address=b"\x01" * 20,
        signature=b"\x02" * 64,
    )
    wire0 = encode_tx_vote(v0)
    noncanon = bytes(amino.field_key(1, amino.TYP3_VARINT)) + bytes(
        amino.varint(0)
    ) + wire0
    d3 = decode_tx_vote(noncanon)
    assert d3._wire_cache is None
    assert encode_tx_vote(d3) == wire0


def test_decode_rejects_empty_timestamp_field_from_cache():
    """Explicit empty field 4 (timestamp) is non-canonical: our encoder
    elides it entirely, so the input bytes must not be cached as wire form."""
    import hashlib

    from txflow_tpu.codec import amino
    from txflow_tpu.types.tx_vote import decode_tx_vote, encode_tx_vote

    tx_key = hashlib.sha256(b"t").digest()
    v = TxVote(
        height=3,
        tx_hash=tx_key.hex().upper(),
        tx_key=tx_key,
        timestamp_ns=0,
        validator_address=b"\x01" * 20,
        signature=b"\x02" * 64,
    )
    wire = encode_tx_vote(v)
    # splice an empty field-4 between field 3 (ends after 2 + 34 bytes of
    # field 1/2 prefix...) — simplest: rebuild with explicit empty ts field
    body = bytearray()
    body += amino.field_key(1, amino.TYP3_VARINT) + amino.varint(3)
    body += amino.field_key(2, amino.TYP3_BYTELEN) + amino.length_prefixed(
        v.tx_hash.encode()
    )
    body += amino.field_key(3, amino.TYP3_BYTELEN) + amino.length_prefixed(tx_key)
    body += amino.field_key(4, amino.TYP3_BYTELEN) + amino.length_prefixed(b"")
    body += amino.field_key(5, amino.TYP3_BYTELEN) + amino.length_prefixed(
        v.validator_address
    )
    body += amino.field_key(6, amino.TYP3_BYTELEN) + amino.length_prefixed(
        v.signature
    )
    d = decode_tx_vote(bytes(body))
    assert d.timestamp_ns == 0
    assert d._wire_cache is None
    assert encode_tx_vote(d) == wire


# -- the ingest's one-pass wire form (types/tx_vote.py ingest_bytes_many, PR 34) --
#
# Byte identity is the contract: WAL records, gossip segments and vote keys
# are the bytes encode_tx_vote / amino.length_prefixed / sha256 give, and
# those are the bytes the amino rule gives when it is spelled out with the
# plain primitives below (what encode_tx_vote was before it read its
# timestamp body from tables).


def _wire_by_the_rule(v: TxVote) -> bytes:
    out = b""
    if v.height != 0:
        out += amino.field_key(1, amino.TYP3_VARINT) + amino.varint(v.height)
    if v.tx_hash:
        out += amino.field_key(2, amino.TYP3_BYTELEN) + amino.length_prefixed(v.tx_hash.encode())
    out += amino.field_key(3, amino.TYP3_BYTELEN) + amino.length_prefixed(v.tx_key or bytes(32))
    seconds, nanos = divmod(v.timestamp_ns, 1_000_000_000)
    ts = b""
    if seconds:
        ts += amino.field_key(1, amino.TYP3_VARINT) + amino.varint(seconds)
    if nanos:
        ts += amino.field_key(2, amino.TYP3_VARINT) + amino.uvarint(nanos)
    if ts:
        out += amino.field_key(4, amino.TYP3_BYTELEN) + amino.length_prefixed(ts)
    if v.validator_address:
        out += amino.field_key(5, amino.TYP3_BYTELEN) + amino.length_prefixed(v.validator_address)
    if v.signature:
        out += amino.field_key(6, amino.TYP3_BYTELEN) + amino.length_prefixed(v.signature)
    return out


def _cold(height=0, tx_hash=None, key=None, ts=1_700_000_000_123_456_789,
          addr=b"\x07" * 20, sig=b"\x09" * 64) -> TxVote:
    """A vote with empty caches, as a feeder or a signer hands it over."""
    import hashlib

    key = hashlib.sha256(b"one-pass").digest() if key is None else key
    v = TxVote(height, key.hex().upper() if tx_hash is None else tx_hash, key, ts, addr, sig)
    assert v._wire_cache is None and v._seg_cache is None and v._vk_cache is None
    return v


# (id, vote fields, which way its bytes come: "fast" is the one-pass
# layout, "general" encode_tx_vote)
_SHAPES = [
    ("canonical-height-0", {}, "fast"),
    ("height-7", {"height": 7}, "fast"),
    ("height-two-byte-varint", {"height": 300}, "fast"),
    ("height-2**40", {"height": 2**40}, "fast"),
    ("height-negative", {"height": -1}, "fast"),
    ("timestamp-0", {"ts": 0}, "fast"),
    ("timestamp-negative", {"ts": -1}, "fast"),
    ("timestamp-go-zero-time", {"ts": -62135596800 * 10**9}, "fast"),
    ("timestamp-whole-seconds", {"ts": 1_700_000_000 * 10**9}, "fast"),
    ("timestamp-999999999-ns", {"ts": 1_700_000_000 * 10**9 + 999_999_999}, "fast"),
    ("timestamp-nanos-only", {"ts": 16_384}, "fast"),
    ("hash-lower-case", {"tx_hash": "ab" * 32}, "fast"),
    ("hash-empty", {"tx_hash": ""}, "general"),
    ("hash-short", {"tx_hash": "A" * 63}, "general"),
    ("hash-long", {"tx_hash": "A" * 200}, "general"),
    ("hash-64-chars-not-64-bytes", {"tx_hash": "é" * 64}, "general"),
    ("key-empty", {"key": b"", "tx_hash": "A" * 64}, "general"),
    ("address-short", {"addr": b"\x07" * 19}, "general"),
    ("address-long", {"addr": b"\x07" * 21}, "general"),
    ("address-empty", {"addr": b""}, "general"),
    ("signature-absent", {"sig": None}, "general"),
    ("signature-empty", {"sig": b""}, "general"),
    ("signature-short", {"sig": b"\x09" * 63}, "general"),
    ("signature-long", {"sig": b"\x09" * 65}, "general"),
]


def _check_ingest_bytes(v: TxVote, way: str) -> None:
    from txflow_tpu.crypto.hash import sha256
    from txflow_tpu.types.tx_vote import ingest_bytes_many

    want = _wire_by_the_rule(v)
    arrived_with = v._wire_cache
    wires, segs, keys, fast, general = ingest_bytes_many([v])
    assert wires == [want]
    assert segs == [amino.length_prefixed(want)]
    assert keys == [sha256(v.signature or b"")]
    assert (fast, general) == {"fast": (1, 0), "general": (0, 1), "primed": (0, 0)}[way]
    if way == "primed":
        assert wires[0] is arrived_with  # never re-encoded
    # the caches as encode_tx_vote, vote_key and the pool have always left
    # them: an unsigned vote's wire form is not kept
    assert v._wire_cache == (want if v.signature is not None else None)
    assert v._seg_cache == segs[0] and v._vk_cache == keys[0]
    assert v.vote_key() == keys[0]
    # the definition agrees, from the cache and without it
    assert encode_tx_vote(v) == want
    assert encode_tx_vote(TxVote(v.height, v.tx_hash, v.tx_key, v.timestamp_ns,
                                 v.validator_address, v.signature)) == want
    # a second pass over the same vote reads what the first left
    again = ingest_bytes_many([v])
    assert again[:3] == (wires, segs, keys)
    assert again[3:] == ((0, 1) if v.signature is None else (0, 0))


@pytest.mark.parametrize("fields,way", [s[1:] for s in _SHAPES], ids=[s[0] for s in _SHAPES])
def test_ingest_bytes_identity_by_field_shape(fields, way):
    _check_ingest_bytes(_cold(**fields), way)


@pytest.mark.parametrize("how", ["decoded", "decoded-many", "over-long-varint", "copied"])
def test_ingest_bytes_identity_of_a_vote_that_arrives_primed(how):
    from txflow_tpu.types.tx_vote import decode_tx_votes_many

    src = _cold(height=5)
    wire = encode_tx_vote(src)
    if how == "decoded":  # the gossip reactor, WAL replay
        v, way = decode_tx_vote(wire), "primed"
    elif how == "decoded-many":
        v, way = decode_tx_votes_many([wire] * 16)[3], "primed"
    elif how == "copied":
        v, way = src.copy(), "primed"
    else:
        # height 5 as a two-byte varint: accepted, but the decoder withholds
        # the wire cache, and what the pool writes is the minimal form
        assert wire[:2] == b"\x08\x05"
        v, way = decode_tx_vote(b"\x08\x85\x00" + wire[2:]), "fast"
        assert v._wire_cache is None and v.height == 5
    _check_ingest_bytes(v, way)
    assert v._wire_cache == wire


def test_ingest_bytes_identity_over_1000_seeded_random_votes():
    import random

    from txflow_tpu.crypto.hash import sha256
    from txflow_tpu.types.tx_vote import ingest_bytes_many

    rng = random.Random(3434)

    def pick(canonical, others):
        return canonical if rng.random() < 0.8 else rng.choice(others)

    votes, n_primed = [], 0
    for _ in range(1000):
        key = rng.randbytes(32)
        v = TxVote(
            pick(0, [1, 127, 128, rng.randrange(2**63), -rng.randrange(1, 2**63)]),
            pick(key.hex().upper(), ["", key.hex(), key.hex()[:40], "Z" * 130]),
            pick(key, [b""]),
            pick(rng.randrange(1_600_000_000 * 10**9, 1_900_000_000 * 10**9),
                 [0, rng.randrange(10**9), -rng.randrange(2**62), rng.randrange(2**62),
                  rng.randrange(1_600_000_000, 1_900_000_000) * 10**9]),
            pick(rng.randbytes(20), [b"", rng.randbytes(19), rng.randbytes(32)]),
            pick(rng.randbytes(64), [None, b"", rng.randbytes(63), rng.randbytes(70)]),
        )
        if rng.random() < 0.2 and v.signature and v.tx_key:
            v = decode_tx_vote(_wire_by_the_rule(v))
            n_primed += v._wire_cache is not None
        votes.append(v)
    want = [_wire_by_the_rule(v) for v in votes]
    wires, segs, keys, fast, general = ingest_bytes_many(votes)
    assert wires == want
    assert segs == [amino.length_prefixed(w) for w in want]
    assert keys == [sha256(v.signature or b"") for v in votes]
    # which way the bytes came adds up, and every way was taken
    assert fast + general + n_primed == 1000
    assert fast > 300 and general > 200 and n_primed > 50
    assert [encode_tx_vote(v) for v in votes] == want
