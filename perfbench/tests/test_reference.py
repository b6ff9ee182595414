"""The reference against hand-made certificates, and its sign bytes against
the program's (the one place the two must render the same bytes)."""

import hashlib

import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from perfbench.harness import corpus, reference

CONFIG = {
    "chain_id": "txflow-bench", "validators": 4, "stake_each": 10,
    "assumed": {"key_seed": "localnet-val"},
    "byzantine": {"validator": 1, "corrupt_one_in": 2},
}


@pytest.fixture(scope="module")
def corp():
    builder = corpus.CorpusBuilder(1)
    try:
        return builder.start(CONFIG, seed=9, n_txs=16, tx_bytes=64, signers=[1, 2, 3]).result()
    finally:
        builder.close()


def rows_for(corp, i, signers):
    hx = corp.tx_key(i).hex().upper()
    return [
        (corpus.address(corp.pub_keys[v]), corp.sig(corp.signer_idx.index(v), i),
         corpus.vote_timestamp(i, corp.n_vals, v), 0, hx)
        for v in signers
    ]


def own_row(corp, i, ts=1_700_000_123_000_000_007):
    hx = corp.tx_key(i).hex().upper()
    sk = Ed25519PrivateKey.from_private_bytes(corpus.validator_seed("localnet-val", 0))
    return (corpus.address(corp.pub_keys[0]),
            sk.sign(corpus.sign_bytes(corp.chain_id, 0, hx, ts)), ts, 0, hx)


def judge(corp, i, rows, **kw):
    tx = corp.tx(i)
    kw.setdefault("stored_tx", tx)
    kw.setdefault("app_value", tx.partition(b"=")[2])
    return reference.Reference(corp).judge(i, rows, kw["stored_tx"], kw["app_value"], own_power=10)


def test_sign_bytes_are_the_programs():
    from txflow_tpu.types.tx_vote import canonical_sign_bytes

    hx = hashlib.sha256(b"tx").hexdigest().upper()
    for height, ts in [(0, corpus.TS_BASE_NS), (0, corpus.TS_BASE_NS + 999), (7, 5), (0, 10**9)]:
        assert corpus.sign_bytes("txflow-bench", height, hx, ts) == canonical_sign_bytes(
            "txflow-bench", height, hx, ts
        )


def test_a_sound_certificate_reads_nought(corp):
    honest = next(i for i in range(16) if not corp.corrupt(1, i))
    got = judge(corp, honest, [own_row(corp, honest)] + rows_for(corp, honest, [1, 2]))
    assert not any(got.values())


def test_the_corrupted_share_is_in_the_corpus_and_is_caught(corp):
    forged = [i for i in range(16) if corp.corrupt(1, i)]
    assert 0 < len(forged) < 16
    i = forged[0]
    got = judge(corp, i, [own_row(corp, i)] + rows_for(corp, i, [1, 2]))
    assert got["cert_invalid_sig"] == 1 and got["cert_short_stake"] == 1
    # the same tx without the forged vote is sound: 3 of 4 still make a quorum
    assert not any(judge(corp, i, [own_row(corp, i)] + rows_for(corp, i, [2, 3])).values())


def test_each_guarantee_has_its_number(corp):
    i = next(i for i in range(16) if not corp.corrupt(1, i))
    full = [own_row(corp, i)] + rows_for(corp, i, [1, 2, 3])
    assert judge(corp, i, full[:2])["cert_short_stake"] == 1
    assert judge(corp, i, full + full[:1])["cert_bad_signer"] == 1  # a signer twice
    other = rows_for(corp, (i + 1) % 16, [2])
    assert judge(corp, i, full[:3] + other)["cert_bad_signer"] == 1  # a vote for another tx
    stranger = (b"\x01" * 20,) + full[1][1:]
    assert judge(corp, i, full[:3] + [stranger])["cert_bad_signer"] == 1
    assert judge(corp, i, full, app_value=b"x")["app_wrong"] == 1
    assert judge(corp, i, full, stored_tx=None)["store_tx_wrong"] == 1
    assert judge(corp, i, None)["cert_missing"] == 1  # a quorum was delivered
