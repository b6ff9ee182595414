"""Out-of-process ABCI: the app runs behind a socket server — in-process
for protocol tests, in a REAL subprocess for the end-to-end commit test —
and the node drives it through RemoteAppConns (the process boundary the
reference opens at node/node.go:576 createAndStartProxyAppConns).
"""

import conftest  # noqa: F401

import hashlib
import subprocess
import sys
import time

import pytest

from txflow_tpu.abci import wire
from txflow_tpu.abci.client import RemoteAppConns
from txflow_tpu.abci.kvstore import KVStoreApplication
from txflow_tpu.abci.server import ABCIServer
from txflow_tpu.abci.types import (
    RequestBeginBlock,
    RequestEndBlock,
    ResponseCheckTx,
    ResponseDeliverTx,
    ResponseEndBlock,
    ResponseInfo,
    ValidatorUpdate,
)


def test_wire_roundtrip():
    """Every message kind survives encode->decode both directions."""
    reqs = [
        (wire.ECHO, {"raw": b"hello"}),
        (wire.FLUSH, {}),
        (wire.INFO, {}),
        (wire.CHECK_TX, {"raw": b"k=v"}),
        (wire.DELIVER_TX, {"raw": b"\x00\xff" * 10}),
        (wire.COMMIT, {}),
        (wire.END_BLOCK, {"height": 42}),
        (wire.QUERY, {"path": "/store", "raw": b"key"}),
    ]
    for kind, kw in reqs:
        enc = wire.encode_request(kind, **kw)
        k2, fields = wire.decode_request(enc)
        assert k2 == kind
        for key, val in kw.items():
            assert fields[key] == val

    enc = wire.encode_request(
        wire.INIT_CHAIN, validators=[ValidatorUpdate(b"\x01" * 32, 10)]
    )
    _, fields = wire.decode_request(enc)
    assert fields["validators"][0].pub_key == b"\x01" * 32
    assert fields["validators"][0].power == 10

    req = RequestBeginBlock(
        hash=b"\xaa" * 20, height=7, proposer_address=b"\xbb" * 20,
        byzantine_validators=[(b"\xcc" * 20, 3)],
    )
    _, fields = wire.decode_request(wire.encode_request(wire.BEGIN_BLOCK, req=req))
    got = fields["req"]
    assert (got.hash, got.height, got.proposer_address) == (
        req.hash, req.height, req.proposer_address
    )
    assert got.byzantine_validators == [(b"\xcc" * 20, 3)]

    # responses
    pairs = [
        (wire.CHECK_TX, ResponseCheckTx(code=3, data=b"d", log="l", gas_wanted=9)),
        (wire.DELIVER_TX, ResponseDeliverTx(code=0, data=b"x", tags=[(b"k", b"v")])),
        (wire.END_BLOCK, ResponseEndBlock(validator_updates=[ValidatorUpdate(b"\x02" * 32, 5)])),
        (wire.INFO, ResponseInfo(data="kv", version="1", last_block_height=4, last_block_app_hash=b"h")),
    ]
    for kind, res in pairs:
        k2, got = wire.decode_response(wire.encode_response(kind, res))
        assert k2 == kind
        assert type(got) is type(res)

    k2, err = wire.decode_response(wire.encode_response(wire.EXCEPTION, "boom"))
    assert k2 == wire.EXCEPTION and isinstance(err, RuntimeError)

    # malformed input raises ValueError, never IndexError (peer-facing)
    for bad in (b"", bytes([wire.INIT_CHAIN]) + b"\xff\xff\xff\xff\xff\xff",
                bytes([99]) + b"x"):
        with pytest.raises(ValueError):
            wire.decode_request(bad)


def test_socket_client_pipelines_and_flush_fence():
    """Async deliveries pipeline on the wire; flush resolves them in
    order; sync calls fence implicitly; app exceptions surface remotely."""

    class Boomy(KVStoreApplication):
        def query(self, path, data):
            if path == "/boom":
                raise RuntimeError("kaboom")
            return super().query(path, data)

    srv = ABCIServer(Boomy())
    srv.start()
    try:
        conns = RemoteAppConns(f"{srv.addr[0]}:{srv.addr[1]}")
        assert conns.consensus.echo(b"ping") == b"ping"

        results = [conns.consensus.deliver_tx_async(b"k%d=v%d" % (i, i)) for i in range(50)]
        conns.consensus.flush()
        assert all(r.value.code == 0 for r in results)
        # an eager .value read (in-process proxy habit) forces the fence
        # itself instead of returning None — drop-in parity
        eager = conns.consensus.deliver_tx_async(b"kx=vx")
        assert eager.value.code == 0
        commit = conns.consensus.commit_sync()
        assert commit.data  # kvstore app hash

        q = conns.query.query_sync("/store", b"k7")
        assert q.value == b"v7"

        with pytest.raises(RuntimeError, match="kaboom"):
            conns.query.query_sync("/boom", b"")
        # connection stays serviceable after a remote exception
        assert conns.query.query_sync("/store", b"k8").value == b"v8"

        # a LARGE pipelined burst must not deadlock the socket pair (the
        # server's dedicated writer thread exists exactly for this: a
        # read-then-write loop wedges once both directions' buffers fill)
        big = [
            conns.consensus.deliver_tx_async(b"big%d=%s" % (i, b"x" * 200))
            for i in range(5000)
        ]
        conns.consensus.flush()
        assert all(r.value.code == 0 for r in big)
        conns.close()
    finally:
        srv.stop()


def test_node_commits_through_subprocess_app():
    """End-to-end across a REAL process boundary: kvstore in a subprocess,
    a node fast-path-commits txs through it, state queries come back over
    the query connection."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "txflow_tpu.abci.server", "--app", "kvstore",
         "--addr", "127.0.0.1:0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]),
    )
    try:
        line = proc.stdout.readline()
        assert "serving kvstore on" in line, line
        addr = line.strip().rsplit(" ", 1)[-1]

        from txflow_tpu.node.node import Node, NodeConfig
        from txflow_tpu.types.priv_validator import MockPV
        from txflow_tpu.types.validator import Validator, ValidatorSet
        from txflow_tpu.types import TxVote
        from txflow_tpu.utils.config import test_config

        pvs = [MockPV(hashlib.sha256(b"abci-%d" % i).digest()) for i in range(4)]
        vs = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs])
        node = Node(
            node_id="n0", chain_id="abci-chain", val_set=vs, app=addr,
            priv_val=pvs[0],
            node_config=NodeConfig(
                config=test_config(), use_device_verifier=False,
                sign_votes=False, enable_consensus=False,
            ),
        )
        assert node.app is None  # the app lives in the other process
        node.start()
        try:
            txs = [b"sub-%d=v%d" % (i, i) for i in range(20)]
            for tx in txs:
                node.mempool.check_tx(tx)
            for tx in txs:
                key = hashlib.sha256(tx).digest()
                for pv in pvs:
                    v = TxVote(height=0, tx_hash=key.hex().upper(), tx_key=key,
                               validator_address=pv.get_address())
                    pv.sign_tx_vote("abci-chain", v)
                    node.tx_vote_pool.check_tx(v)
            deadline = time.monotonic() + 60 * conftest.WAIT_FACTOR
            for tx in txs:
                h = hashlib.sha256(tx).hexdigest().upper()
                while not node.tx_store.has_tx(h):
                    assert time.monotonic() < deadline, "commit timeout"
                    time.sleep(0.01)
            # a certificate is a decision-time fact; the DeliverTx to the
            # other process runs a beat later on the committer thread
            while not node.txflow.commits_drained():
                assert time.monotonic() < deadline, "apply timeout"
                time.sleep(0.01)
            # the app state lives in the subprocess: query round trip
            res = node.proxy_app.query.query_sync("/store", b"sub-3")
            assert res.value == b"v3"
            assert node.txflow.app_hash  # commit hashes flowed back
        finally:
            node.stop()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_exception_mid_drain_keeps_stream_aligned():
    """An app EXCEPTION for one pipelined request must not desync the
    connection: later pipelined responses still resolve, the fence's own
    frame is consumed, and the NEXT call reads its own response — not a
    stale frame (r4 advisor: _drain_pending previously abandoned the
    remaining responses in the socket)."""

    class Exploding(KVStoreApplication):
        def deliver_tx(self, tx: bytes) -> ResponseDeliverTx:
            if tx.startswith(b"boom"):
                raise RuntimeError("mid-pipeline kaboom")
            return super().deliver_tx(tx)

    srv = ABCIServer(Exploding())
    srv.start()
    try:
        conns = RemoteAppConns(f"{srv.addr[0]}:{srv.addr[1]}")
        c = conns.consensus
        rs = [
            c.deliver_tx_async(b"a=1"),
            c.deliver_tx_async(b"boom"),
            c.deliver_tx_async(b"b=2"),
            c.deliver_tx_async(b"c=3"),
        ]
        with pytest.raises(RuntimeError, match="mid-pipeline kaboom"):
            c.flush()
        # entries after the failed one were still drained and resolved
        assert rs[0].value.code == 0
        assert rs[2].value.code == 0
        assert rs[3].value.code == 0
        # the failed entry re-raises its recorded error on read
        with pytest.raises(RuntimeError, match="mid-pipeline kaboom"):
            _ = rs[1].value
        # and the connection is ALIGNED: a fresh sync call gets its own
        # response, not the leftover of an unread frame
        assert c.deliver_tx_async(b"d=4").value.code == 0
        commit = c.commit_sync()
        assert commit.data
        conns.close()
    finally:
        srv.stop()


def test_async_callback_fires_at_fence_without_forcing_flush():
    """Registering a callback must not itself force a flush round-trip;
    callbacks fire in submit order when a fence resolves the entries
    (reference ReqRes callback-at-flush semantics)."""
    srv = ABCIServer(KVStoreApplication())
    srv.start()
    try:
        conns = RemoteAppConns(f"{srv.addr[0]}:{srv.addr[1]}")
        seen = []
        for i in range(5):
            conns.mempool.check_tx_async(
                b"cb%d=v" % i, callback=lambda r, i=i: seen.append((i, r.code))
            )
        assert seen == []  # nothing fired yet: no fence has run
        conns.mempool.flush()
        assert seen == [(i, 0) for i in range(5)]
        conns.close()
    finally:
        srv.stop()


def test_check_tx_fast_path_flag_crosses_the_socket():
    """An out-of-process app's block-only verdict (fast_path=False) must
    survive the wire round trip — losing it would let validators fast-
    sign EndBlock-coupled txs (wire.py uv(block_only) field)."""

    class Flagger(KVStoreApplication):
        def check_tx(self, tx: bytes) -> ResponseCheckTx:
            if tx.startswith(b"block-only:"):
                return ResponseCheckTx(gas_wanted=7, fast_path=False)
            return ResponseCheckTx(gas_wanted=1)

    srv = ABCIServer(Flagger())
    srv.start()
    try:
        conns = RemoteAppConns(f"{srv.addr[0]}:{srv.addr[1]}")
        r1 = conns.mempool.check_tx_sync(b"block-only:val")
        assert r1.fast_path is False and r1.gas_wanted == 7
        r2 = conns.mempool.check_tx_sync(b"normal=1")
        assert r2.fast_path is True and r2.gas_wanted == 1
        conns.close()
    finally:
        srv.stop()
