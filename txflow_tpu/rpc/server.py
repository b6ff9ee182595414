"""RPC server: the operator/client HTTP surface (reference node/node.go:
878-1007 — RPC listeners + the Prometheus metrics server).

Minimal JSON-over-HTTP core mirroring the tendermint RPC methods the
reference exposes for the fast path, plus the Prometheus text exposition:

- GET/POST /broadcast_tx?tx=0x.. | ?tx="str"   -> submit a tx (CheckTx)
- GET  /status                                  -> node/chain/height info
- GET  /tx?hash=HEX                             -> committed-tx lookup
      (fast-path certificate: votes + commit presence)
- GET  /subscribe_tx?hash=HEX&timeout=SECS      -> long-poll until the tx
      commits (the WS tx-subscription analog; resolves on EITHER path)
- GET  /block?height=N                          -> block + hashes
- GET  /blockchain                              -> store height + base
- GET  /validators                              -> current validator set
- GET  /abci_query?path=P&data=0x..             -> app query
- GET  /metrics                                 -> Prometheus exposition
- GET  /health                                  -> degraded-mode + trace digest
- GET  /trace                                   -> span ring dump (trace/)

Served by a stdlib ThreadingHTTPServer — the runtime dependency story
stays 'none'; handlers only touch thread-safe node surfaces.
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..admission import ErrDuplicateTx, ErrOverloaded
from ..pool.mempool import ErrMempoolIsFull, ErrTxInCache
from ..trace.tracer import NULL_TRACER, SPAN_RPC
from ..utils.clock import monotonic


class RPCError(Exception):
    """Raise from a route handler to control the HTTP status/headers of
    the reply (the generic handler-exception path is a blanket 500)."""

    def __init__(self, status: int, body: dict, headers: dict | None = None):
        super().__init__(f"rpc error {status}")
        self.status = status
        self.body = body
        self.headers = headers or {}


def _parse_tx_param(raw: str) -> bytes:
    """tendermint-style tx param: 0x-hex or a (possibly quoted) string."""
    if raw.startswith("0x") or raw.startswith("0X"):
        return bytes.fromhex(raw[2:])
    if len(raw) >= 2 and raw[0] == raw[-1] == '"':
        raw = raw[1:-1]
    return raw.encode()


def _event_json(ev) -> dict:
    """JSON-safe projection of an event-bus payload for WS streaming."""
    d = ev.data
    if hasattr(d, "tx_hash"):
        return {
            "type": ev.type,
            "height": d.height,
            "hash": d.tx_hash,
            "code": d.result_code,
        }
    blk = getattr(d, "block", None)
    if blk is not None:
        return {
            "type": ev.type,
            "height": blk.height,
            "hash": blk.hash().hex().upper(),
        }
    return {"type": ev.type}


# request-body and concurrency caps (reference MaxOpenConnections /
# request limits, node/node.go:925-929)
MAX_BODY_BYTES = 1 << 20
MAX_OPEN_CONNECTIONS = 128


class _BoundedHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a hard cap on concurrent connections:
    past MAX_OPEN_CONNECTIONS the listener sheds new sockets with a
    best-effort 503 instead of spawning an unbounded thread per
    connection (a connection flood would otherwise exhaust threads/
    filedescriptors). Shed connections are COUNTED — a silent bare reset
    made overload invisible to both clients and dashboards."""

    daemon_threads = True
    # bounded kernel accept backlog: under a connection flood the excess
    # queues (briefly) in the kernel instead of growing handler state
    request_queue_size = 64

    _REJECT_BODY = json.dumps({"error": "too many open connections"}).encode()
    _REJECT_RESPONSE = (
        b"HTTP/1.1 503 Service Unavailable\r\n"
        b"Content-Type: application/json\r\n"
        b"Content-Length: " + str(len(_REJECT_BODY)).encode() + b"\r\n"
        b"Retry-After: 1\r\n"
        b"Connection: close\r\n\r\n" + _REJECT_BODY
    )

    def __init__(self, addr, handler, metrics_registry=None):
        self._conn_sem = threading.Semaphore(MAX_OPEN_CONNECTIONS)
        self._rejected = None
        if metrics_registry is not None:
            self._rejected = metrics_registry.counter(
                "rpc", "rejected_total",
                "connections shed at the RPC listener (over the open-conn cap)",
            )
        super().__init__(addr, handler)

    def process_request(self, request, client_address):
        if not self._conn_sem.acquire(blocking=False):
            if self._rejected is not None:
                self._rejected.add(1)
            try:
                # minimal pre-built 503 so the client sees backpressure,
                # not a bare RST; best-effort (the flood case is exactly
                # when sends may fail)
                request.sendall(self._REJECT_RESPONSE)
            except OSError:
                pass
            try:
                request.close()
            except OSError:
                pass
            return
        super().process_request(request, client_address)

    def process_request_thread(self, request, client_address):
        try:
            super().process_request_thread(request, client_address)
        finally:
            self._conn_sem.release()


class RPCServer:
    def __init__(self, node, host: str = "127.0.0.1", port: int = 0, debug=None):
        """debug: expose /debug/* hooks. Default: only on loopback binds —
        the reference likewise serves pprof only when ProfListenAddress is
        explicitly configured (node/node.go:724-728); an open profiling/
        trace-to-arbitrary-dir endpoint must never face a network."""
        self.node = node
        self.debug = (host in ("127.0.0.1", "::1", "localhost")) if debug is None else debug
        rpc = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # Read timeout: without it, an idle client parks its handler
            # thread in readline() forever, and MAX_OPEN_CONNECTIONS
            # permits are never released — 128 silent sockets would
            # hard-lock the whole RPC (r5 review). The reference pairs
            # MaxOpenConnections with read timeouts the same way. The
            # websocket path lifts it after the upgrade (long-lived).
            timeout = 30

            def log_message(self, fmt, *args):  # quiet by default
                pass

            def _reply(self, obj, code=200, headers=None):
                payload = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(payload)

            def _reply_text(self, text: str, code=200):
                payload = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_POST(self):
                # Body size cap (reference caps request sizes via its RPC
                # server config, node/node.go:925-929): an oversized body
                # is rejected with 413 and the connection dropped — partly
                # reading it and dispatching anyway would desync keep-
                # alive framing, and reading it all would buffer
                # attacker-sized payloads.
                try:
                    n = int(self.headers.get("Content-Length", "0") or "0")
                except ValueError:
                    n = 0
                if n > MAX_BODY_BYTES:
                    # tell the client explicitly (Connection: close) and
                    # drain a bounded slice of the in-flight body before
                    # closing — an immediate close with unread bytes in
                    # the receive buffer emits RST and destroys the 413
                    # before the client reads it (r5 review)
                    self.close_connection = True
                    payload = json.dumps(
                        {"error": "request body too large"}
                    ).encode()
                    self.send_response(413)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(payload)))
                    self.send_header("Connection", "close")
                    self.end_headers()
                    self.wfile.write(payload)
                    self.wfile.flush()
                    try:
                        self.connection.settimeout(2)
                        remaining = min(n, 4 * MAX_BODY_BYTES)
                        while remaining > 0:
                            got = self.rfile.read(min(remaining, 65536))
                            if not got:
                                break
                            remaining -= len(got)
                    except OSError:
                        pass
                    return
                chunked = bool(self.headers.get("Transfer-Encoding"))
                if chunked:
                    # chunked bodies are not parsed: dispatch, then drop
                    # the connection so unread chunk bytes can never be
                    # misread as the next request line (and the size cap
                    # cannot be bypassed by omitting Content-Length)
                    self.close_connection = True
                # drain the body BEFORE dispatch: with keep-alive enabled,
                # unread body bytes would be parsed as the next request
                # line on this connection
                try:
                    if n > 0:
                        self.rfile.read(n)
                except OSError:
                    pass
                self.do_GET()
                if chunked:
                    # bounded drain of unread chunk bytes before close —
                    # close() with data in the receive buffer emits RST,
                    # which can destroy the response in flight (same
                    # hazard the 413 path drains for)
                    try:
                        self.wfile.flush()
                        self.connection.settimeout(2)
                        for _ in range(64):
                            if not self.rfile.read(65536):
                                break
                    except OSError:
                        pass

            def do_GET(self):
                try:
                    parsed = urllib.parse.urlparse(self.path)
                    q = {
                        k: v[0]
                        for k, v in urllib.parse.parse_qs(parsed.query).items()
                    }
                    route = parsed.path.rstrip("/") or "/"
                    if route == "/websocket":
                        # event-stream upgrade (reference WS subscriptions,
                        # node/node.go:914-922); takes over the socket
                        rpc._serve_websocket(self)
                        self.close_connection = True
                        return
                    handler = rpc._routes.get(route)
                    if handler is None:
                        self._reply({"error": f"unknown path {route}"}, 404)
                        return
                    result = handler(q)
                    if route == "/metrics":
                        self._reply_text(result)
                    else:
                        self._reply({"result": result})
                except RPCError as e:
                    # typed status replies (429 overload + Retry-After)
                    self._reply(e.body, e.status, e.headers)
                except Exception as e:
                    self._reply({"error": repr(e)}, 500)

        self._httpd = _BoundedHTTPServer(
            (host, port), Handler,
            metrics_registry=getattr(node, "metrics_registry", None),
        )
        self.addr = self._httpd.server_address
        self._thread: threading.Thread | None = None
        self._routes = {
            "/broadcast_tx": self._broadcast_tx,
            "/broadcast_tx_sync": self._broadcast_tx,
            "/broadcast_tx_commit": self._broadcast_tx_commit,
            "/status": self._status,
            "/tx": self._tx,
            "/subscribe_tx": self._subscribe_tx,
            "/block": self._block,
            "/blockchain": self._blockchain,
            "/validators": self._validators,
            "/abci_query": self._abci_query,
            "/tx_search": self._tx_search,
            "/metrics": self._metrics,
            "/health": self._health,
            "/trace": self._trace,
            # rpccore.Routes parity (reference node/node.go:898-986)
            "/commit": self._commit,
            "/genesis": self._genesis,
            "/net_info": self._net_info,
            "/commit_log": self._commit_log,
            "/block_results": self._block_results,
            "/unconfirmed_txs": self._unconfirmed_txs,
            "/num_unconfirmed_txs": self._num_unconfirmed_txs,
            "/consensus_state": self._consensus_state,
            "/dump_consensus_state": self._dump_consensus_state,
            "/broadcast_evidence": self._broadcast_evidence,
        }
        if self.debug:
            # profiling hooks (reference links net/http/pprof and starts a
            # JAX-profiler-analog on demand, node/node.go:724-728)
            self._routes["/debug/stacks"] = self._debug_stacks
            self._routes["/debug/jax_profile"] = self._debug_jax_profile

    # -- lifecycle --

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="rpc-http", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()

    # -- handlers --

    @staticmethod
    def _dup_result(key: bytes) -> dict:
        """The ONE duplicate-submission reply: edge-dedup hits and
        mempool-cache hits both answer through here, so the two paths are
        byte-identical on the wire (ISSUE 6 satellite)."""
        return {"hash": key.hex().upper(), "code": 0, "duplicate": True}

    @staticmethod
    def _overload_error(retry_after: float) -> RPCError:
        return RPCError(
            429,
            {"error": "overloaded", "retry_after": retry_after},
            {"Retry-After": str(max(1, int(round(retry_after))))},
        )

    def _broadcast_tx(self, q: dict) -> dict:
        t0 = monotonic()  # rpc_ingest: request parsed -> tx in the mempool
        tx = _parse_tx_param(q["tx"])
        key = hashlib.sha256(tx).digest()
        adm = getattr(self.node, "admission", None)
        if adm is not None:
            try:
                adm.admit_rpc(tx, key)
            except ErrDuplicateTx:
                return self._dup_result(key)
            except ErrOverloaded as e:
                raise self._overload_error(e.retry_after)
        try:
            self.node.broadcast_tx(tx)
        except ErrTxInCache:
            # first sighting at THIS edge but the pool already has it
            # (e.g. it arrived by gossip): same dup verdict as the edge
            return self._dup_result(key)
        except ErrMempoolIsFull:
            # pool rejected after edge admit: release the dedup slot so
            # the client's post-Retry-After resubmit isn't dup-bounced
            if adm is not None:
                adm.forget(key)
            raise self._overload_error(
                adm.cfg.retry_after if adm is not None else 1.0
            )
        except Exception:
            if adm is not None:
                adm.forget(key)
            raise
        tx_hash = key.hex().upper()
        tr = getattr(self.node, "tracer", NULL_TRACER)
        if tr.active and tr.sampled_key(key):
            # ends at the mempool insert (where the tx was anchored and
            # its sign_wait starts), so the two tile
            tr.span(tx_hash, SPAN_RPC, t0, tr.anchored(tx_hash) or monotonic())
        return {"hash": tx_hash, "code": 0}

    def _broadcast_tx_commit(self, q: dict) -> dict:
        """Submit + wait for the commit in one call (tendermint's
        broadcast_tx_commit; resolves via EITHER commit path)."""
        res = self._broadcast_tx(q)
        sub = self._subscribe_tx(
            {"hash": res["hash"], "timeout": q.get("timeout", "30")}
        )
        return {**res, **sub}

    def _status(self, q: dict) -> dict:
        node = self.node
        from .. import version

        return {
            "node_info": {
                "id": node.node_id,
                "network": node.chain_id,
                "protocol_version": {
                    "p2p": version.P2P_PROTOCOL,
                    "block": version.BLOCK_PROTOCOL,
                    "app": version.ABCI_SEMVER,
                },
                "version": version.SEMVER,
            },
            "sync_info": {
                "latest_block_height": node.block_store.height(),
                "latest_app_hash": node.chain_state.app_hash.hex(),
                "fast_path_height": node.committed_height_view,
            },
            "validator_info": {
                "address": (
                    node.priv_val.get_address().hex().upper()
                    if node.priv_val
                    else ""
                ),
            },
            "health": self._health_summary(),
        }

    def _health_summary(self) -> dict:
        """Degraded-mode digest for /status: verifier + watchdog counters
        without the full per-peer detail of /health."""
        mon = getattr(self.node, "health", None)
        if mon is None:
            return {"monitored": False}
        snap = mon.snapshot()
        return {
            "monitored": True,
            "healthy": snap["healthy"],
            "watchdog_firings": snap["watchdog"]["firings"],
            "peer_reconnects": snap["peers"]["reconnects"],
            "verifier": snap["verifier"],
        }

    def _health(self, q: dict) -> dict:
        """Full degraded-mode registry snapshot (health/registry.py) plus
        the trace digest: p50/p99/p999 per span family, the engine's
        stage families (pool_wait .. route, device_busy, gc_pause) among
        them, the leak counter, and what each ring dropped (``dropped``
        per-tx spans, ``stage_dropped`` stage spans). {} sections when
        the node runs without a monitor/tracer."""
        mon = getattr(self.node, "health", None)
        out = dict(mon.snapshot()) if mon is not None else {}
        tracer = getattr(self.node, "tracer", None)
        if tracer is not None:
            out["trace"] = tracer.digest()
        return out

    def _trace(self, q: dict) -> dict:
        """Span-ring dump for cross-node merge (tools/trace_export.py,
        tools/soak.py --overload leak assertion)."""
        tracer = getattr(self.node, "tracer", None)
        if tracer is None:
            return {
                "node": self.node.node_id,
                "base_wall_ns": 0,
                "base_mono": 0.0,
                "spans": [],
                "open_spans": 0,
            }
        return tracer.dump(self.node.node_id)

    def _tx(self, q: dict) -> dict:
        tx_hash = q["hash"].upper()
        votes = self.node.tx_store.load_tx_votes(tx_hash)
        commit = self.node.tx_store.load_tx_commit(tx_hash)
        committed = self.node.txflow.is_tx_committed(tx_hash)
        return {
            "hash": tx_hash,
            "committed": committed,
            "votes": len(votes) if votes else 0,
            "has_commit_cert": commit is not None,
        }

    # -- WebSocket event streaming (RFC 6455 server side, no deps) --

    _WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"

    def _serve_websocket(self, handler) -> None:
        """Upgrade + event pump. Client subscribes with one JSON text
        frame {"subscribe": "Tx" | "NewBlock"}; the server then streams
        each matching event as a JSON text frame until the client closes.
        The reference serves the same capability via its WS RPC
        subscriptions (node/node.go:914-922)."""
        import base64
        import hashlib as _hl
        import struct as _st

        key = handler.headers.get("Sec-WebSocket-Key", "")
        if handler.headers.get("Upgrade", "").lower() != "websocket" or not key:
            handler.send_response(400)
            handler.end_headers()
            return
        # long-lived stream: lift the HTTP read timeout set on the
        # handler class (idle subscribers are legitimate here; the pump
        # has its own liveness handling)
        try:
            handler.connection.settimeout(None)
        except OSError:
            pass
        accept = base64.b64encode(
            _hl.sha1((key + self._WS_GUID).encode()).digest()
        ).decode()
        handler.wfile.write(
            (
                "HTTP/1.1 101 Switching Protocols\r\n"
                "Upgrade: websocket\r\nConnection: Upgrade\r\n"
                f"Sec-WebSocket-Accept: {accept}\r\n\r\n"
            ).encode()
        )
        handler.wfile.flush()
        conn, wf = handler.connection, handler.wfile

        # Bounded IO from here on: a stalled client (suspended process,
        # half-dead link) must not wedge send_frame forever — the pump
        # would hold wlock, the reader's pong path would block behind it,
        # and unsubscribe would never run. Sends now fail after the
        # timeout; reads below retry on it (idle is normal for a reader).
        conn.settimeout(30.0)

        # Drain whatever the handshake's buffered reader already pulled
        # off the socket (a pipelining client's first frames can sit in
        # handler.rfile): everything after this comes from conn.recv,
        # which — unlike BufferedReader.read under a timeout — never
        # discards partially-read data.
        rf = handler.rfile
        buffered = b""
        try:
            conn.settimeout(0.001)
            while True:
                peeked = rf.peek(1)
                if not peeked:
                    break
                buffered += rf.read(len(peeked))
        except (TimeoutError, OSError):
            pass  # rfile buffer empty: the raw peek hit the socket
        finally:
            conn.settimeout(30.0)

        # one writer lock: the event pump and the reader thread's pongs
        # both send frames
        wlock = threading.Lock()

        def send_frame(opcode: int, payload: bytes) -> None:
            hdr = bytes([0x80 | opcode])
            n = len(payload)
            if n < 126:
                hdr += bytes([n])
            elif n < 1 << 16:
                hdr += bytes([126]) + _st.pack(">H", n)
            else:
                hdr += bytes([127]) + _st.pack(">Q", n)
            with wlock:
                wf.write(hdr + payload)
                wf.flush()

        def read_exact(n: int, deadline: float | None = None) -> bytes:
            """EOF mid-frame is a close, never a partial read or a
            mid-frame resume: a short read would desync RFC6455 framing
            for the rest of the connection (r3 advisor medium). Timeouts
            between frames are idle, not errors — retry (listen-only
            clients are legitimate), unless a deadline is given."""
            nonlocal buffered
            out = b""
            while len(out) < n:
                if buffered:
                    take = min(n - len(out), len(buffered))
                    out += buffered[:take]
                    buffered = buffered[take:]
                    continue
                try:
                    chunk = conn.recv(n - len(out))
                except TimeoutError:
                    if deadline is not None and time.monotonic() > deadline:
                        raise ConnectionError("websocket read deadline")
                    continue  # idle poll; partial bytes stay in `out`
                if not chunk:
                    raise ConnectionError("websocket closed")
                out += chunk
            return out

        def recv_frame(deadline: float | None = None):
            b0 = read_exact(1, deadline)[0]
            opcode = b0 & 0x0F
            b1 = read_exact(1, deadline)[0]
            n = b1 & 0x7F
            if n == 126:
                (n,) = _st.unpack(">H", read_exact(2, deadline))
            elif n == 127:
                (n,) = _st.unpack(">Q", read_exact(8, deadline))
            # inbound frames are a small JSON subscribe + <=125-byte
            # control frames: a client-declared 64-bit length must not
            # make read_exact buffer unbounded memory
            if n > 1 << 20:
                raise ConnectionError(f"websocket frame too large ({n} bytes)")
            mask = read_exact(4, deadline) if b1 & 0x80 else b""  # clients MUST mask
            data = read_exact(n, deadline) if n else b""
            if mask:
                data = bytes(c ^ mask[i % 4] for i, c in enumerate(data))
            return opcode, data

        try:
            # the subscribe frame must arrive promptly; after that the
            # client may stay silent forever (listen-only)
            opcode, data = recv_frame(deadline=time.monotonic() + 30.0)
            if opcode != 1:  # expect a text subscribe frame
                send_frame(8, b"")
                return
            req = json.loads(data or b"{}")
            event_type = req.get("subscribe", "Tx")
            if event_type not in ("Tx", "NewBlock"):
                send_frame(1, json.dumps({"error": "unknown event"}).encode())
                send_frame(8, b"")
                return
            # the subscription must be released on EVERY exit (an ack
            # write to a just-reset connection raises before the pump
            # starts): everything past subscribe() runs under the finally
            sub = self.node.event_bus.subscribe(event_type)
            tr = getattr(self.node, "tracer", NULL_TRACER)
            try:
                send_frame(1, json.dumps({"subscribed": event_type}).encode())

                # reader thread: blocking control-frame loop (ping/close).
                # Event delivery must not gate on client chatter — the old
                # interleaved 0.5 s recv poll capped delivery at ~2
                # events/s and a timeout landing mid-frame desynced the
                # framing.
                closed = threading.Event()

                def reader() -> None:
                    try:
                        while True:
                            op, payload = recv_frame()
                            if op == 8:  # close
                                return
                            if op == 9:  # ping -> pong
                                send_frame(10, payload)
                    except (ConnectionError, OSError, _st.error):
                        pass
                    finally:
                        closed.set()

                rt = threading.Thread(target=reader, name="ws-reader", daemon=True)
                rt.start()
                while not closed.is_set():
                    ev = sub.get(timeout=0.5)
                    if ev is not None:
                        send_frame(1, json.dumps(_event_json(ev)).encode())
                        # a sampled commit's publish span ends here: its
                        # frame is on this subscriber's socket (the first
                        # subscriber to send closes it)
                        tr.finish(ev.span)
            finally:
                self.node.event_bus.unsubscribe(event_type, sub)
                for ev in sub.close():
                    tr.abandon(ev.span)  # queued, never sent: no leak
                # handler return closes the socket, unblocking the reader
        except (BrokenPipeError, ConnectionError, OSError):
            pass

    def _subscribe_tx(self, q: dict) -> dict:
        """Long-poll tx-commit subscription (the WS subscribe analog:
        reference EventDataTx over the event bus, node/node.go:914-922)."""
        tx_hash = q["hash"].upper()
        timeout = min(float(q.get("timeout", "25")), 60.0)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.node.txflow.is_tx_committed(tx_hash):
                return {"hash": tx_hash, "committed": True}
            time.sleep(0.02)
        return {"hash": tx_hash, "committed": False, "timeout": True}

    def _block(self, q: dict) -> dict:
        height = int(q["height"])
        block = self.node.block_store.load_block(height)
        if block is None:
            raise ValueError(f"no block at height {height}")
        return {
            "height": block.height,
            "hash": block.hash().hex().upper(),
            "num_txs": len(block.txs),
            "num_vtxs": len(block.vtxs),
            "txs": [tx.hex() for tx in block.txs],
            "vtxs": [tx.hex() for tx in block.vtxs],
            "app_hash": block.header.app_hash.hex(),
            "proposer": block.header.proposer_address.hex().upper(),
        }

    def _blockchain(self, q: dict) -> dict:
        store = self.node.block_store
        return {"base": store.base(), "height": store.height()}

    # -- rpccore.Routes parity (reference node/node.go:898-986) --

    def _commit(self, q: dict) -> dict:
        """Block header + the commit that sealed it — the light-client /
        commit-certificate flow (reference rpccore /commit). Defaults to
        the latest committed height; serves the SEEN commit for the head
        (the canonical commit lives in the NEXT block's LastCommit)."""
        store = self.node.block_store
        height = int(q.get("height", store.height()))
        block = store.load_block(height)
        if block is None:
            raise ValueError(f"no block at height {height}")
        commit = store.load_block_commit(height)
        canonical = commit is not None
        if commit is None:
            commit = store.load_seen_commit(height)
        if commit is None:
            raise ValueError(f"no commit for height {height}")
        h = block.header
        return {
            "header": {
                "chain_id": h.chain_id,
                "height": h.height,
                "time_ns": h.time_ns,
                "last_block_id": h.last_block_id.hex().upper(),
                "app_hash": h.app_hash.hex(),
                "validators_hash": h.validators_hash.hex(),
                "evidence_hash": h.evidence_hash.hex(),
                "proposer_address": h.proposer_address.hex().upper(),
            },
            "block_id": block.hash().hex().upper(),
            "canonical": canonical,
            "commit": {
                "block_id": commit.block_id.hex().upper(),
                "precommits": [
                    {
                        "height": v.height,
                        "round": v.round,
                        "block_id": v.block_id.hex().upper(),
                        "timestamp_ns": v.timestamp_ns,
                        "validator_address": v.validator_address.hex().upper(),
                        "signature": (v.signature or b"").hex(),
                    }
                    for v in commit.precommits
                ],
            },
        }

    def _genesis(self, q: dict) -> dict:
        import json as _json

        return {"genesis": _json.loads(self.node.genesis.to_json())}

    def _commit_log(self, q: dict) -> dict:
        """This node's fast-path commit-order log (store S: rows). There
        is no GLOBAL total order across fast-path nodes (sync/manager.py)
        — each node's log is its own decision order — so cross-node
        checks compare committed SETS plus per-node prefix stability; the
        WAN matrix (tools/soak.py --wan-matrix) reads this per scenario.
        ``start``/``count`` window the read; ``count=0`` returns just the
        total + digest-to-date (cheap prefix-equality probe)."""
        store = self.node.tx_store
        total = store.seq_count()
        start = max(int(q.get("start", 0)), 0)
        count = int(q.get("count", max(total - start, 0)))
        hashes = [h for _seq, h in store.committed_range(start, count)]
        digest = hashlib.sha256()
        for h in store.committed_range(0, total):
            digest.update(h[1].encode())
        return {
            "total": total,
            "start": start,
            "hashes": hashes,
            "digest": digest.hexdigest(),
        }

    def _net_info(self, q: dict) -> dict:
        peers = self.node.switch.peers()
        return {
            "listening": True,
            "n_peers": len(peers),
            "peers": [
                {
                    "node_id": p.node_id,
                    "is_outbound": p.outbound,
                }
                for p in peers
            ],
        }

    def _block_results(self, q: dict) -> dict:
        """Per-tx ABCI results for a committed block (reference rpccore
        /block_results, served from the persisted ABCIResponses)."""
        height = int(q["height"])
        raw = self.node.state_store.load_abci_responses(height)
        if raw is None:
            raise ValueError(f"no results for height {height}")
        import json as _json

        d = _json.loads(raw)
        return {
            "height": height,
            "deliver_tx": d.get("deliver_tx", []),
            "validator_updates": d.get("validator_updates", []),
        }

    def _unconfirmed_txs(self, q: dict) -> dict:
        limit = min(int(q.get("limit", "30")), 100)
        txs = self.node.mempool.reap_max_txs(limit)
        return {
            "n_txs": len(txs),
            "total": self.node.mempool.size(),
            "total_bytes": self.node.mempool.txs_bytes(),
            "txs": [tx.hex() for tx in txs],
        }

    def _num_unconfirmed_txs(self, q: dict) -> dict:
        return {
            "total": self.node.mempool.size(),
            "total_bytes": self.node.mempool.txs_bytes(),
            "vote_pool": self.node.tx_vote_pool.size(),
        }

    def _round_state_obj(self, full: bool) -> dict:
        cs = self.node.consensus
        if cs is None:
            raise ValueError("consensus is disabled on this node")
        rs = cs.round_state()
        out = {
            "height": rs.height,
            "round": rs.round,
            "step": int(rs.step),
            "start_time_ns": rs.start_time_ns,
            "locked_round": rs.locked_round,
            "valid_round": rs.valid_round,
            "proposal": rs.proposal is not None,
            "proposal_block": (
                rs.proposal_block.hash().hex().upper()
                if rs.proposal_block is not None
                else ""
            ),
        }
        if full:
            _, _, votes = cs.current_round_data()
            out["votes"] = [
                {
                    "height": v.height,
                    "round": v.round,
                    "type": v.type,
                    "block_id": v.block_id.hex().upper(),
                    "validator_address": v.validator_address.hex().upper(),
                }
                for v in votes
            ]
            out["validators"] = [
                {"address": v.address.hex().upper(), "power": v.voting_power}
                for v in (rs.validators or [])
            ]
        return out

    def _consensus_state(self, q: dict) -> dict:
        return {"round_state": self._round_state_obj(full=False)}

    def _dump_consensus_state(self, q: dict) -> dict:
        return {"round_state": self._round_state_obj(full=True)}

    def _broadcast_evidence(self, q: dict) -> dict:
        """Submit evidence (hex of the wire form); verified + gossiped via
        the evidence pool (reference rpccore /broadcast_evidence)."""
        from ..types.evidence import decode_evidence

        raw = q["evidence"]
        ev = decode_evidence(bytes.fromhex(raw[2:] if raw.startswith("0x") else raw))
        added, err = self.node.evidence_pool.add(ev)
        if err is not None:
            raise ValueError(f"invalid evidence: {err}")
        return {"hash": ev.hash().hex().upper(), "added": added}

    def _validators(self, q: dict) -> dict:
        vs = self.node.chain_state.validators
        return {
            "count": len(vs),
            "total_power": vs.total_voting_power(),
            "validators": [
                {
                    "address": v.address.hex().upper(),
                    "pub_key": v.pub_key.hex(),
                    "power": v.voting_power,
                }
                for v in vs
            ],
        }

    def _abci_query(self, q: dict) -> dict:
        data = q.get("data", "")
        raw = bytes.fromhex(data[2:]) if data.startswith("0x") else data.encode()
        res = self.node.proxy_app.query.query_sync(q.get("path", ""), raw)
        return {
            "code": res.code,
            "key": (res.key or b"").hex(),
            "value": (res.value or b"").hex(),
            "height": res.height,
        }

    def _tx_search(self, q: dict) -> dict:
        """Indexer queries (reference tx indexer service): by height or by
        tag (?height=N | ?key=app.key&value=hex-or-str)."""
        idx = self.node.tx_indexer
        if idx is None:
            raise ValueError("tx indexing is disabled on this node")
        if "height" in q:
            hashes = idx.by_height(int(q["height"]))
        elif "key" in q:
            val = q.get("value", "")
            vraw = bytes.fromhex(val[2:]) if val.startswith("0x") else val.encode()
            hashes = idx.search(q["key"].encode(), vraw)
        else:
            raise ValueError("tx_search needs ?height= or ?key=&value=")
        return {"txs": [idx.get(h) for h in hashes], "total": len(hashes)}

    def _debug_stacks(self, q: dict) -> dict:
        """All-thread stack dump — the pprof-goroutine analog for a Python
        runtime (reference serves net/http/pprof when ProfListenAddress is
        set)."""
        import sys
        import traceback

        frames = sys._current_frames()
        stacks = {}
        for t in threading.enumerate():
            f = frames.get(t.ident)
            stacks[t.name] = (
                traceback.format_stack(f) if f is not None else ["<no frame>"]
            )
        return {"threads": stacks, "count": len(stacks)}

    def _debug_jax_profile(self, q: dict) -> dict:
        """Start/stop a JAX profiler trace (the XLA-level tracing hook):
        ?action=start&dir=/tmp/trace | ?action=stop. A session started
        here holds, beside the device's planes (the step's module is
        ``jit_txflow_verify_tally``, its scopes decompress /
        double_scalar_mul / encode_compare / tally), the engine's stage
        annotations in the host plane, on the same clock: pool_wait,
        host_prep, dispatch, collect_wait, route, each with step= and
        votes=, so an idle gap of the device can be put down to the stage
        that covers it (perfbench/study/gap_stages.py reads both)."""
        import jax.profiler

        import os.path

        action = q.get("action", "start")
        if action == "start":
            trace_dir = q.get("dir", "/tmp/txflow-jax-trace")
            # confine trace output: profiling must not become an
            # arbitrary-path write primitive
            if not os.path.abspath(trace_dir).startswith("/tmp/"):
                raise ValueError("trace dir must live under /tmp/")
            jax.profiler.start_trace(trace_dir)
            return {"tracing": True, "dir": trace_dir}
        jax.profiler.stop_trace()
        return {"tracing": False}

    def _metrics(self, q: dict) -> str:
        return self.node.metrics_registry.expose()
