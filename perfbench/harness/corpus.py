"""Transactions and pre-signed peer votes, made from the seed.

Everything is held as a few flat ``bytes`` blobs, not as objects, so that
Python's collector has nothing of the benchmark's to walk. Signing runs in
worker processes that import this module only: no JAX, no ``txflow_tpu``.

The canonical sign bytes are this file's own rendering of go-txflow's
``CanonicalTxVote`` (amino, length-prefixed): height (elided at 0), the
tx hash as uppercase hex, a zero 32-byte tx key, the timestamp, the chain
id. The reference uses the same function, so a vote that the benchmark
signs, the program verifies and the reference re-verifies agree on the
bytes only if the program renders them the same way.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import multiprocessing
from dataclasses import dataclass

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey
from cryptography.hazmat.primitives.serialization import Encoding, PublicFormat

TS_BASE_NS = 1_700_000_000_000_000_000
SIG_BYTES = 64
KEY_BYTES = 32
_ZERO_KEY = bytes(32)


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def sign_bytes(chain_id: str, height: int, tx_hash_hex: str, timestamp_ns: int) -> bytes:
    """Length-prefixed amino ``CanonicalTxVote``."""
    body = bytearray()
    if height:
        body += b"\x09" + (height & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")
    hb = tx_hash_hex.encode()
    body += b"\x12" + _uvarint(len(hb)) + hb
    body += b"\x1a\x20" + _ZERO_KEY
    seconds, nanos = divmod(timestamp_ns, 1_000_000_000)
    ts = b""
    if seconds:
        ts += b"\x08" + _uvarint(seconds & 0xFFFFFFFFFFFFFFFF)
    if nanos:
        ts += b"\x10" + _uvarint(nanos)
    if ts:
        body += b"\x22" + _uvarint(len(ts)) + ts
    cb = chain_id.encode()
    if cb:
        body += b"\x2a" + _uvarint(len(cb)) + cb
    return _uvarint(len(body)) + bytes(body)


def validator_seed(key_seed: str, index: int) -> bytes:
    """Private seed of validator ``index``: sha256(key_seed + index), the
    derivation ``LocalNet`` uses for its default keys."""
    return hashlib.sha256(key_seed.encode() + b"%d" % index).digest()


def public_key(seed: bytes) -> bytes:
    return Ed25519PrivateKey.from_private_bytes(seed).public_key().public_bytes(
        Encoding.Raw, PublicFormat.Raw
    )


def address(pub_key: bytes) -> bytes:
    return hashlib.sha256(pub_key).digest()[:20]


def make_tx(tag: bytes, i: int, tx_bytes: int) -> bytes:
    """``key=value`` kvstore tx of exactly tx_bytes."""
    head = b"%s-k%08d=" % (tag, i)
    fill = hashlib.sha256(head).hexdigest().encode()
    return head + (fill * (tx_bytes // len(fill) + 1))[: tx_bytes - len(head)]


def vote_timestamp(i: int, n_vals: int, v: int) -> int:
    return TS_BASE_NS + i * n_vals + v


def is_corrupt(seed: int, i: int, share_den: int) -> bool:
    """Whether the Byzantine validator's vote on tx i is corrupted: one in
    share_den, spread by a hash of (seed, i) so every seed has the same
    share in another order."""
    if share_den <= 0:
        return False
    h = hashlib.sha256(b"corrupt-%d-%d" % (seed, i)).digest()
    return int.from_bytes(h[:4], "little") % share_den == 0


def powers_of(config: dict) -> list[int]:
    """Every validator's stake, by index: the configuration's ``stake``, a
    list of ``validators`` positive whole numbers written out in its file,
    or ``stake_each`` for all of them where it has no such list. The one
    place the benchmark reads a configuration's stake: the node's validator
    set, the signed corpus and the reference's sums all take it from here."""
    n_vals = int(config["validators"])
    stake = config.get("stake")
    if stake is None:
        return [int(config["stake_each"])] * n_vals
    if len(stake) != n_vals:
        raise ValueError(f"stake lists {len(stake)} validators, the configuration has {n_vals}")
    if any(type(p) is not int or p <= 0 for p in stake):
        raise ValueError(f"stake has to be positive whole numbers: {stake!r}")
    return list(stake)


def byzantine_of(config: dict, fault: str | None) -> dict | None:
    """The peer that corrupts signatures in this run: the configuration's
    own, or, where the net is honest, the one its file plants for the
    accept-all control alone (a verifier that accepts everything shows
    only where something invalid is sent)."""
    own = config.get("byzantine")
    if own is None and fault == "accept_all":
        return config.get("control_byzantine")
    return own


def _sign_range(args) -> tuple[int, bytes, list[bytes]]:
    """Worker: txs [lo, hi) — returns (lo, tx key blob, one signature blob
    per signer)."""
    (chain_id, tag, tx_bytes, lo, hi, signer_seeds, signer_idx, n_vals,
     byz_idx, byz_den, seed) = args
    keys = [Ed25519PrivateKey.from_private_bytes(s) for s in signer_seeds]
    key_blob = bytearray()
    sigs = [bytearray() for _ in keys]
    for i in range(lo, hi):
        tx_key = hashlib.sha256(make_tx(tag, i, tx_bytes)).digest()
        key_blob += tx_key
        hx = tx_key.hex().upper()
        for k, (sk, v) in enumerate(zip(keys, signer_idx)):
            sig = sk.sign(sign_bytes(chain_id, 0, hx, vote_timestamp(i, n_vals, v)))
            if v == byz_idx and is_corrupt(seed, i, byz_den):
                sig = sig[:7] + bytes([sig[7] ^ 0xFF]) + sig[8:]
            sigs[k] += sig
    return lo, bytes(key_blob), [bytes(s) for s in sigs]


@dataclass
class Corpus:
    """n_txs transactions and, for each signer, one signature per tx."""

    chain_id: str
    tag: bytes
    seed: int
    tx_bytes: int
    n_txs: int
    n_vals: int
    signer_idx: list[int]  # validator index (key derivation order) per signer
    pub_keys: list[bytes]  # of every validator, by index
    powers: list[int]
    byz_idx: int
    byz_den: int
    tx_keys: bytes = b""  # n_txs x 32
    sigs: list[bytes] = None  # per signer: n_txs x 64

    def tx(self, i: int) -> bytes:
        return make_tx(self.tag, i, self.tx_bytes)

    def tx_key(self, i: int) -> bytes:
        return self.tx_keys[i * KEY_BYTES : (i + 1) * KEY_BYTES]

    def sig(self, k: int, i: int) -> bytes:
        return self.sigs[k][i * SIG_BYTES : (i + 1) * SIG_BYTES]

    def corrupt(self, v: int, i: int) -> bool:
        return v == self.byz_idx and is_corrupt(self.seed, i, self.byz_den)


class CorpusBuilder:
    """Signs in worker processes while the caller does something else."""

    def __init__(self, workers: int):
        self._pool = concurrent.futures.ProcessPoolExecutor(
            max_workers=workers, mp_context=multiprocessing.get_context("spawn")
        )
        self._workers = workers

    def start(self, config: dict, seed: int, n_txs: int, tx_bytes: int,
              signers: list[int], fault: str | None = None) -> "PendingCorpus":
        n_vals = int(config["validators"])
        key_seed = config["assumed"]["key_seed"]
        seeds = [validator_seed(key_seed, v) for v in range(n_vals)]
        byz = byzantine_of(config, fault) or {}
        corpus = Corpus(
            chain_id=config["chain_id"], tag=b"pb%x" % seed, seed=seed,
            tx_bytes=tx_bytes, n_txs=n_txs, n_vals=n_vals, signer_idx=list(signers),
            pub_keys=[public_key(s) for s in seeds],
            powers=powers_of(config),
            byz_idx=int(byz.get("validator", -1)),
            byz_den=int(byz.get("corrupt_one_in", 0)),
        )
        per = max(256, -(-n_txs // (4 * self._workers)))
        futures = [
            self._pool.submit(_sign_range, (
                corpus.chain_id, corpus.tag, tx_bytes, lo, min(lo + per, n_txs),
                [seeds[v] for v in signers], list(signers), n_vals,
                corpus.byz_idx, corpus.byz_den, seed,
            ))
            for lo in range(0, n_txs, per)
        ]
        return PendingCorpus(corpus, futures)

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)


class PendingCorpus:
    def __init__(self, corpus: Corpus, futures):
        self._corpus = corpus
        self._futures = futures

    def result(self) -> Corpus:
        parts = sorted(f.result() for f in self._futures)
        c = self._corpus
        c.tx_keys = b"".join(p[1] for p in parts)
        c.sigs = [b"".join(p[2][k] for p in parts) for k in range(len(c.signer_idx))]
        return c
