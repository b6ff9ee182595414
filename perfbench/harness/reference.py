"""The plain reference: what a correct validator must have decided.

Independent of the program: OpenSSL's ed25519 through ``cryptography``, this
directory's own rendering of the sign bytes, plain Python sets and sums. It
takes nothing the program made except the answers it is judging: the
certificate rows of the TxStore, the tx bytes stored beside them, the
kvstore's content and the commit events the client saw.

Guarantees held (the configuration files state them): a tx is committed
only with a certificate of valid votes of more than 2/3 of the stake, by
distinct validators of the set, each for this tx; no invalid vote in any
certificate; every acknowledged tx whose valid votes reach a quorum is
committed, and readable from the TxStore and the app.
"""

from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from . import corpus as corpus_mod

# every number compared is a count of violations: the comparison is exact
LIMIT = 0
NUMBERS = (
    "never_committed", "cert_missing", "cert_invalid_sig", "cert_bad_signer",
    "cert_short_stake", "store_tx_wrong", "app_wrong", "event_wrong",
)


def verify(pub_key: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != 64:
        return False
    try:
        Ed25519PublicKey.from_public_bytes(pub_key).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


class Reference:
    def __init__(self, corpus: corpus_mod.Corpus):
        self.corpus = corpus
        self.by_address = {
            corpus_mod.address(pk): (v, pk, corpus.powers[v])
            for v, pk in enumerate(corpus.pub_keys)
        }
        self.total = sum(corpus.powers)
        self.quorum = self.total * 2 // 3 + 1

    def delivered_valid_stake(self, i: int, own_power: int = 0) -> int:
        """Stake of the delivered pre-signed votes on tx i that verify,
        plus own_power where the node signs its own vote in the window."""
        c = self.corpus
        hx = c.tx_key(i).hex().upper()
        stake = own_power
        for k, v in enumerate(c.signer_idx):
            msg = corpus_mod.sign_bytes(
                c.chain_id, 0, hx, corpus_mod.vote_timestamp(i, c.n_vals, v)
            )
            if verify(c.pub_keys[v], msg, c.sig(k, i)):
                stake += c.powers[v]
        return stake

    def judge(self, i: int, rows, stored_tx, app_value, own_power: int = 0) -> dict:
        """Violations on tx i. rows: the certificate as (address,
        signature, timestamp_ns, height, tx_hash) tuples, or None when the
        TxStore holds none. Returns a count per name in NUMBERS (without
        never_committed and event_wrong, which need the run's clock)."""
        c = self.corpus
        out = dict.fromkeys(NUMBERS, 0)
        tx = c.tx(i)
        hx = c.tx_key(i).hex().upper()
        if rows is None:
            if self.delivered_valid_stake(i, own_power) >= self.quorum:
                out["cert_missing"] = 1
            return out
        stake, seen = 0, set()
        for addr, sig, ts, height, tx_hash in rows:
            who = self.by_address.get(addr)
            if who is None or addr in seen or tx_hash != hx:
                out["cert_bad_signer"] += 1
                continue
            seen.add(addr)
            _, pk, power = who
            if verify(pk, corpus_mod.sign_bytes(c.chain_id, height, hx, ts), sig or b""):
                stake += power
            else:
                out["cert_invalid_sig"] += 1
        if stake < self.quorum:
            out["cert_short_stake"] = 1
        if stored_tx != tx:
            out["store_tx_wrong"] = 1
        key, _, value = tx.partition(b"=")
        if app_value != value:
            out["app_wrong"] = 1
        return out
