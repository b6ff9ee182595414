"""TxVote: a per-transaction validator vote (reference types/tx_vote.go).

Sign bytes are amino ``MarshalBinaryLengthPrefixed(CanonicalTxVote)`` where
``CanonicalTxVote{Height fixed64, TxHash, TxKey, Timestamp, ChainID}`` — and,
exactly as in the reference, ``CanonicalizeTxVote`` does NOT copy the vote's
TxKey (types/tx_vote.go:185-192), so field 3 always serializes as 32 zero
bytes. Preserving that quirk is required for signature compatibility.
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from hashlib import sha256 as _sha256

from ..codec import amino
from ..crypto import ed25519
from ..crypto.hash import ADDRESS_SIZE, address_hash, sha256

# Maximum amino-encoded vote size, including overhead (types/tx_vote.go:17).
MAX_VOTE_BYTES = 223
# tendermint types.MaxSignatureSize (v0.31).
MAX_SIGNATURE_SIZE = 64

_ZERO_TXKEY = bytes(32)

_SEMANTIC_FIELDS = frozenset(
    ("height", "tx_hash", "tx_key", "timestamp_ns", "validator_address", "signature")
)


def canonical_sign_bytes(
    chain_id: str, height: int, tx_hash: str, timestamp_ns: int
) -> bytes:
    """Length-prefixed amino encoding of CanonicalTxVote.

    Hand-tightened: this runs once per (vote, node) on the verify path
    (a top host cost in the r3 pipeline profile). Field-key bytes are the
    precomputed amino constants — (fnum << 3) | typ3, all < 0x80 — and the
    layout is pinned by the golden vectors in tests/test_tx_vote.py.
    """
    body = bytearray()
    if height != 0:
        body += b"\x09"  # field 1, TYP3_8BYTE
        body += (height & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")
    if tx_hash:
        hb = tx_hash.encode()
        body += b"\x12"  # field 2, TYP3_BYTELEN
        body += amino.uvarint(len(hb))
        body += hb
    # TxKey: fixed-size array, never elided; canonicalization leaves it zero.
    body += b"\x1a\x20"  # field 3, TYP3_BYTELEN, len 32
    body += _ZERO_TXKEY
    ts_body = amino.encode_time_body(timestamp_ns)
    if ts_body:
        body += b"\x22"  # field 4, TYP3_BYTELEN
        body += amino.uvarint(len(ts_body))
        body += ts_body
    if chain_id:
        cb = chain_id.encode()
        body += b"\x2a"  # field 5, TYP3_BYTELEN
        body += amino.uvarint(len(cb))
        body += cb
    return amino.length_prefixed(bytes(body))


@dataclass
class TxVote:
    height: int
    tx_hash: str  # uppercase hex of sha256(tx)
    tx_key: bytes  # sha256(tx), 32 bytes
    timestamp_ns: int = field(default_factory=_time.time_ns)
    validator_address: bytes = b""
    signature: bytes | None = None
    # encode caches: a signed vote is immutable, and re-deriving sign bytes
    # and wire bytes per engine step measured as a top host cost at bench
    # scale (r3 step profile). Signers mutate fields BEFORE the first
    # encode, so lazy first-use caching is safe; copies carry the caches
    # (any later field write clears them via __setattr__).
    _sb_cache: tuple | None = field(
        default=None, repr=False, compare=False
    )
    _wire_cache: bytes | None = field(default=None, repr=False, compare=False)
    _vk_cache: bytes | None = field(default=None, repr=False, compare=False)
    # length-prefixed wire form (gossip frame segment): decoded votes are
    # shared process-wide by the reactor wire cache, so caching the seg on
    # the object makes every co-located pool's ingest reuse one build
    _seg_cache: bytes | None = field(default=None, repr=False, compare=False)

    def __setattr__(self, name, value):
        # any semantic-field write invalidates the encode caches, so even
        # post-signing tampering (byzantine tests) can never serve stale
        # bytes
        if name in _SEMANTIC_FIELDS:
            object.__setattr__(self, "_sb_cache", None)
            object.__setattr__(self, "_wire_cache", None)
            object.__setattr__(self, "_vk_cache", None)
            object.__setattr__(self, "_seg_cache", None)
        object.__setattr__(self, name, value)

    def sign_bytes(self, chain_id: str) -> bytes:
        c = self._sb_cache
        if c is not None and c[0] == chain_id:
            return c[1]
        sb = canonical_sign_bytes(
            chain_id, self.height, self.tx_hash, self.timestamp_ns
        )
        if self.signature is not None:  # immutable once signed
            self._sb_cache = (chain_id, sb)
        return sb

    def verify(self, chain_id: str, pub_key: bytes) -> str | None:
        """Returns None if valid, else an error string (types/tx_vote.go:110-119)."""
        if address_hash(pub_key) != self.validator_address:
            return "invalid validator address"
        if not self.signature or not ed25519.verify(
            pub_key, self.sign_bytes(chain_id), self.signature
        ):
            return "invalid signature"
        return None

    def validate_basic(self) -> str | None:
        if self.height < 0:
            return "negative height"
        if len(self.validator_address) != ADDRESS_SIZE:
            return (
                f"expected ValidatorAddress size to be {ADDRESS_SIZE} bytes, "
                f"got {len(self.validator_address)} bytes"
            )
        if not self.signature:
            return "signature is missing"
        if len(self.signature) > MAX_SIGNATURE_SIZE:
            return f"signature is too big (max: {MAX_SIGNATURE_SIZE})"
        return None

    def size(self) -> int:
        return len(encode_tx_vote(self))

    def copy(self) -> "TxVote":
        # caches travel with the copy: they only describe the semantic
        # fields, and any later field write clears them via __setattr__ —
        # dropping them here made every commit-certificate encode a full
        # re-serialize (r3 pipeline profile)
        v = TxVote.__new__(TxVote)
        oset = object.__setattr__
        oset(v, "height", self.height)
        oset(v, "tx_hash", self.tx_hash)
        oset(v, "tx_key", self.tx_key)
        oset(v, "timestamp_ns", self.timestamp_ns)
        oset(v, "validator_address", self.validator_address)
        oset(v, "signature", self.signature)
        oset(v, "_sb_cache", self._sb_cache)
        oset(v, "_wire_cache", self._wire_cache)
        oset(v, "_vk_cache", self._vk_cache)
        oset(v, "_seg_cache", self._seg_cache)
        return v

    def vote_key(self) -> bytes:
        """sha256(signature) — dedup cache key (txvotepool/txvotepool.go:467-469).

        Cached: the pool, the engine's purge bookkeeping, and gossip dedup
        all re-derive it for the same immutable vote (~180k calls per 12k
        commits in the r3 profile). __setattr__ clears it on any semantic
        field write, like the encode caches."""
        k = self._vk_cache
        if k is None:
            k = sha256(self.signature or b"")
            object.__setattr__(self, "_vk_cache", k)
        return k


def sign_bytes_many(votes: list["TxVote"], chain_id: str) -> list[bytes]:
    """Sign bytes for a whole drain batch, priming each vote's cache.

    Cache misses batch through the native codec (native/codec.c, ~0.1 us
    per vote vs ~4 us for the per-vote Python encode — a top-5 host cost
    at bench rates, r5 profile); without a C compiler the Python path
    computes them one by one, same bytes either way (parity pinned by
    tests/test_native_prep.py)."""
    out: list[bytes | None] = [None] * len(votes)
    miss: list[int] = []
    for i, v in enumerate(votes):
        c = v._sb_cache
        if c is not None and c[0] == chain_id:
            out[i] = c[1]
        else:
            miss.append(i)
    if miss:
        from .. import native

        batch = native.sign_bytes_batch(
            [votes[i].height for i in miss],
            [votes[i].tx_hash for i in miss],
            [votes[i].timestamp_ns for i in miss],
            chain_id,
        )
        if batch is not None:
            for j, i in enumerate(miss):
                if batch[j] is None:
                    # field bounds exceeded (hostile vote): per-item
                    # Python fallback — same bytes, no native fast path
                    out[i] = votes[i].sign_bytes(chain_id)
                    continue
                out[i] = batch[j]
                if votes[i].signature is not None:  # immutable once signed
                    object.__setattr__(
                        votes[i], "_sb_cache", (chain_id, batch[j])
                    )
        else:
            for i in miss:
                out[i] = votes[i].sign_bytes(chain_id)
    return out  # type: ignore[return-value]


def encode_tx_vote(vote: TxVote) -> bytes:
    """Amino MarshalBinaryBare of the full TxVote struct (WAL/wire form)."""
    if vote._wire_cache is not None:
        return vote._wire_cache
    body = bytearray()
    if vote.height != 0:
        body += amino.field_key(1, amino.TYP3_VARINT)
        body += amino.varint(vote.height)
    if vote.tx_hash:
        body += amino.field_key(2, amino.TYP3_BYTELEN)
        body += amino.length_prefixed(vote.tx_hash.encode())
    body += amino.field_key(3, amino.TYP3_BYTELEN)
    body += amino.length_prefixed(vote.tx_key or _ZERO_TXKEY)
    ts_body = amino.encode_time_body(vote.timestamp_ns)
    if ts_body:
        body += amino.field_key(4, amino.TYP3_BYTELEN)
        body += amino.length_prefixed(ts_body)
    if vote.validator_address:
        body += amino.field_key(5, amino.TYP3_BYTELEN)
        body += amino.length_prefixed(vote.validator_address)
    if vote.signature:
        body += amino.field_key(6, amino.TYP3_BYTELEN)
        body += amino.length_prefixed(vote.signature)
    out = bytes(body)
    if vote.signature is not None:  # immutable once signed
        vote._wire_cache = out
    return out


# length prefix of every wire form the one-pass layout can produce (190
# bytes of fixed fields, at most 11 of height and 24 of timestamp)
_LEN_PREFIX = [amino.uvarint(n) for n in range(256)]
# key and length of the timestamp field by its body's length; an empty
# body elides the field
_TS_HEAD = [b""] + [b"\x22" + amino.uvarint(n) for n in range(1, 32)]


def ingest_bytes_many(
    votes: list[TxVote],
) -> tuple[list[bytes], list[bytes], list[bytes], int, int]:
    """What the vote pool's ingest needs of a frame, one pass a vote:
    ``(wires, segs, keys, fast, general)`` — each vote's wire form
    (``encode_tx_vote``, which stays the definition), its gossip segment
    (``amino.length_prefixed`` of that) and its dedup key
    (``vote_key()``), byte for byte, and which way the wire forms came.

    A vote that arrives with its wire form set (gossip decode, WAL
    replay) is never re-encoded: it gets the segment and the key it
    lacks and counts under neither number. A vote of the canonical shape
    (a 64-byte hash, a 32-byte key, a 20-byte address, a 64-byte
    signature: every length prefix is then a constant) is laid out in
    one join around its timestamp body, counted in ``fast``. Any other
    shape — a field length off those, no signature — goes through
    ``encode_tx_vote``, counted in ``general``. The three caches are left
    primed as ``encode_tx_vote``, ``vote_key`` and the pool have always
    left them (an unsigned vote's wire form is returned, not cached).
    Parity over field shapes and seeded random votes:
    tests/test_tx_vote.py."""
    wires: list[bytes] = []
    segs: list[bytes] = []
    keys: list[bytes] = []
    fast = general = 0
    oset = object.__setattr__
    join = b"".join
    time_body = amino.encode_time_body
    for v in votes:
        wire = v._wire_cache
        sig = v.signature
        if wire is None:
            try:
                h = v.tx_hash.encode()
                k = v.tx_key
                a = v.validator_address
                if len(h) == 64 and len(k) == 32 and len(a) == 20 and len(sig) == 64:
                    ht = v.height
                    tb = time_body(v.timestamp_ns)
                    wire = join((
                        b"\x08" + amino.varint(ht) if ht != 0 else b"",
                        b"\x12\x40", h,
                        b"\x1a\x20", k,
                        _TS_HEAD[len(tb)], tb,
                        b"\x2a\x14", a,
                        b"\x32\x40", sig,
                    ))
            except (AttributeError, TypeError):
                wire = None  # a field that is no bytes at all: the definition judges it
            if wire is None:
                general += 1
                wire = encode_tx_vote(v)
            else:
                fast += 1
                oset(v, "_wire_cache", wire)  # signed, so immutable
        wires.append(wire)
        seg = v._seg_cache
        if seg is None:
            n = len(wire)
            seg = (_LEN_PREFIX[n] if n < 256 else amino.uvarint(n)) + wire
            oset(v, "_seg_cache", seg)
        segs.append(seg)
        key = v._vk_cache
        if key is None:  # vote_key()'s body
            key = _sha256(sig or b"").digest()
            oset(v, "_vk_cache", key)
        keys.append(key)
    return wires, segs, keys, fast, general


def _uv(data: bytes, pos: int, end: int) -> tuple[int, int, bool]:
    """Uvarint continuation path (Go binary.Uvarint overflow rules).

    Returns (value, new_pos, minimal): ``minimal`` is False for over-long
    encodings (a trailing 0x00 continuation group). They are ACCEPTED —
    same accept-set as Go — but the caller must refuse the wire cache,
    since our encoder would emit the shorter form."""
    n = 0
    shift = 0
    while True:
        if pos >= end:
            raise ValueError("truncated uvarint")
        b = data[pos]
        pos += 1
        if shift == 63 and b > 1:
            raise ValueError("uvarint overflows 64 bits")
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, pos, b != 0
        shift += 7
        if shift > 63:
            raise ValueError("uvarint overflows 64 bits")


def decode_tx_vote(data: bytes) -> TxVote:
    """Hand-rolled single-pass parser.

    This runs once per gossiped vote per node — the top pipeline cost in
    the r3 stub-verify profile — so it inlines the one-byte-varint fast
    path and constructs the TxVote via object.__setattr__ instead of the
    guarded dataclass path. The accept-set is identical to the AminoReader
    formulation (pinned by tests/test_tx_vote.py + test_amino.py).

    ``canonical`` tracks whether the input is exactly the byte string our
    own encoder emits (fields strictly ordered, no unknown fields, no
    explicitly-encoded defaults, minimal varints, normalized time body):
    only then are the input bytes cached as the vote's wire form, so
    re-gossip and TxStore certificate encoding never re-serialize.
    Non-canonical peer encodings fall back to a real re-serialize like
    the reference (Go amino re-marshals from the struct). The cache
    contract is exact — cached bytes are bit-identical to
    encode_tx_vote's output — and fuzz-pinned (tests/test_fuzz_codec.py).
    """
    pos = 0
    end = len(data)
    height = 0
    tx_hash = ""
    tx_key = _ZERO_TXKEY
    timestamp_ns = 0
    validator_address = b""
    signature = None
    canonical = True
    prev_fnum = 0
    try:
        while pos < end:
            b = data[pos]
            if b < 0x80:
                key = b
                pos += 1
            else:
                key, pos, mini = _uv(data, pos, end)
                if not mini:
                    canonical = False
            fnum = key >> 3
            typ3 = key & 7
            if fnum <= prev_fnum:
                canonical = False
            prev_fnum = fnum
            if typ3 == 2:  # BYTELEN
                b = data[pos]
                if b < 0x80:
                    ln = b
                    pos += 1
                else:
                    ln, pos, mini = _uv(data, pos, end)
                    if not mini:
                        canonical = False
                npos = pos + ln
                if npos > end:
                    raise ValueError("truncated byte field")
                seg = data[pos:npos]
                pos = npos
                if fnum == 2:
                    tx_hash = seg.decode()
                    if not tx_hash:
                        canonical = False
                elif fnum == 3:
                    if ln != 32:
                        # Go amino unmarshals into [sha256.Size]byte and
                        # errors on any other length; keep the wire
                        # accept-set identical.
                        raise ValueError(f"TxKey must be 32 bytes, got {ln}")
                    tx_key = seg
                elif fnum == 4:
                    timestamp_ns, ts_canon = _decode_ts_body(seg)
                    if not ts_canon:
                        canonical = False
                elif fnum == 5:
                    validator_address = seg
                    if not seg:
                        canonical = False
                elif fnum == 6:
                    signature = seg
                    if not seg:
                        canonical = False
                else:
                    canonical = False  # unknown BYTELEN field: skipped
            elif typ3 == 0:  # VARINT
                b = data[pos]
                if b < 0x80:
                    v = b
                    pos += 1
                else:
                    v, pos, mini = _uv(data, pos, end)
                    if not mini:
                        canonical = False
                if fnum == 1:
                    height = v - (1 << 64) if v >= 1 << 63 else v
                    if height == 0:
                        canonical = False
                else:
                    canonical = False  # unknown varint field: skipped
            elif typ3 == 1:  # 8BYTE
                if pos + 8 > end:
                    raise ValueError("truncated fixed64")
                pos += 8
                canonical = False  # no fixed64 field in TxVote
            else:
                raise ValueError(f"unknown typ3 {typ3}")
    except IndexError:
        raise ValueError("truncated uvarint") from None
    vote = TxVote.__new__(TxVote)
    oset = object.__setattr__
    oset(vote, "height", height)
    oset(vote, "tx_hash", tx_hash)
    oset(vote, "tx_key", tx_key)
    oset(vote, "timestamp_ns", timestamp_ns)
    oset(vote, "validator_address", validator_address)
    oset(vote, "signature", signature)
    oset(vote, "_sb_cache", None)
    oset(vote, "_vk_cache", None)
    oset(vote, "_seg_cache", None)
    if signature and canonical and tx_key is not _ZERO_TXKEY:
        oset(vote, "_wire_cache", bytes(data))
    else:
        oset(vote, "_wire_cache", None)
    return vote


def decode_tx_votes_many(segs: list[bytes]) -> list[TxVote]:
    """Batch decode of gossiped vote segments; raises ValueError on the
    FIRST undecodable segment (same contract as per-seg decode_tx_vote —
    the receive path stops the peer).

    The amino field walk runs in one C call (native/codec.c, a strict
    accept-set mirror of decode_tx_vote, fuzz-pinned by
    tests/test_fuzz_codec.py); Python slices the located fields and
    constructs the TxVote objects — including the strict UTF-8 check of
    tx_hash, which str() performs anyway. Exactness corners the C side
    flags (bit2: timestamps beyond int64) and builds missing native
    support fall back to the Python decoder, identical results.
    """
    from .. import native

    # crossover: the C call's fixed cost (concat + numpy buffers + ctypes
    # marshalling, ~45 us) beats per-seg Python only from ~16-32 segs
    # (measured r5 review: 49 us/vote at n=1, 5.1 at n=32 vs 5.2 pure
    # Python) — steady-state frames with few cache misses stay on the
    # inline decoder
    if len(segs) < 16:
        return [decode_tx_vote(s) for s in segs]
    fields = native.decode_votes_fields(segs)
    if fields is None:
        return [decode_tx_vote(s) for s in segs]
    (
        heights, timestamps, hash_off, hash_len, key_off,
        addr_off, addr_len, sig_off, sig_len, flags, concat,
    ) = fields
    out: list[TxVote] = []
    oset = object.__setattr__
    for i, seg in enumerate(segs):
        f = flags[i]
        if not f & 1:
            raise ValueError("undecodable tx vote segment")
        if f & 4:  # exactness corner: defer to the Python decoder
            out.append(decode_tx_vote(seg))
            continue
        ho = hash_off[i]
        tx_hash = (
            concat[ho : ho + hash_len[i]].decode() if ho >= 0 else ""
        )  # strict utf-8: raises like decode_tx_vote (stops the peer)
        ko = key_off[i]
        tx_key = concat[ko : ko + 32] if ko >= 0 else _ZERO_TXKEY
        ao = addr_off[i]
        addr = concat[ao : ao + addr_len[i]] if ao >= 0 else b""
        so = sig_off[i]
        sig = concat[so : so + sig_len[i]] if so >= 0 else None
        vote = TxVote.__new__(TxVote)
        oset(vote, "height", int(heights[i]))
        oset(vote, "tx_hash", tx_hash)
        oset(vote, "tx_key", tx_key)
        oset(vote, "timestamp_ns", int(timestamps[i]))
        oset(vote, "validator_address", addr)
        oset(vote, "signature", sig)
        oset(vote, "_sb_cache", None)
        oset(vote, "_vk_cache", None)
        oset(vote, "_seg_cache", None)
        if sig and (f & 2) and ko >= 0:
            oset(vote, "_wire_cache", seg)
        else:
            oset(vote, "_wire_cache", None)
        out.append(vote)
    return out


def _decode_ts_body(body: bytes) -> tuple[int, bool]:
    """(unix_ns, canonical): canonical iff body == encode_time_body(ns)."""
    if not body:
        # encode_time_body(0) elides the whole field — an explicit empty
        # field 4 is never something our encoder emits
        return 0, False
    pos = 0
    end = len(body)
    seconds = 0
    nanos = 0
    canonical = True
    prev = 0
    while pos < end:
        b = body[pos]
        if b < 0x80:
            key = b
            pos += 1
        else:
            key, pos, mini = _uv(body, pos, end)
            if not mini:
                canonical = False
        fnum = key >> 3
        typ3 = key & 7
        if fnum <= prev:
            canonical = False
        prev = fnum
        if typ3 == 0:
            b = body[pos] if pos < end else 0x80
            if b < 0x80:
                v = b
                pos += 1
            else:
                v, pos, mini = _uv(body, pos, end)
                if not mini:
                    canonical = False
            if fnum == 1:
                seconds = v - (1 << 64) if v >= 1 << 63 else v
                if seconds == 0:
                    canonical = False
            elif fnum == 2:
                nanos = v
                if not 0 < v < 1_000_000_000:
                    canonical = False
            else:
                canonical = False
        elif typ3 == 1:
            if pos + 8 > end:
                raise ValueError("truncated fixed64")
            pos += 8
            canonical = False
        elif typ3 == 2:
            b = body[pos] if pos < end else 0x80
            if b < 0x80:
                ln = b
                pos += 1
            else:
                ln, pos, mini = _uv(body, pos, end)
                if not mini:
                    canonical = False
            if pos + ln > end:
                raise ValueError("truncated byte field")
            pos += ln
            canonical = False
        else:
            raise ValueError(f"unknown typ3 {typ3}")
    return seconds * 1_000_000_000 + nanos, canonical
