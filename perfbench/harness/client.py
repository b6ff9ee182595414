"""The served cells' client, a process of its own.

It never imports JAX or the program, so it cannot starve under the node's
interpreter lock, nor touch the chip. It sends ``POST /broadcast_tx`` on
the schedule it is given, whatever the replies do (an open loop: tx i is
due ``offsets_ns[i]`` after ``t0_ns``), and listens on the node's
``/websocket`` for the commit events. Times are ``time.monotonic_ns()``,
which on Linux is one clock for every process of the machine, so the parent
can set the schedule and read the result.

stdin: one JSON object (see ``run``). stdout: one JSON object.
"""

from __future__ import annotations

import base64
import http.client
import json
import os
import socket
import struct
import sys
import threading
import time

from . import corpus as corpus_mod


def sleep_until(t_ns: int) -> None:
    """Sleep to 1.5 ms before t_ns, then yield in a loop: a plain sleep
    wakes about a millisecond late, every time."""
    while True:
        rem = t_ns - time.monotonic_ns()
        if rem <= 0:
            return
        time.sleep((rem - 1_500_000) / 1e9 if rem > 2_000_000 else 0)


class EventListener(threading.Thread):
    """Subscribes to ``Tx`` events and stamps each on arrival."""

    def __init__(self, host: str, port: int):
        super().__init__(name="ws-listener", daemon=True)
        self.seen: dict[str, tuple[int, int]] = {}  # hash -> (t_ns, code)
        self.error: str | None = None
        self._sock = socket.create_connection((host, port), timeout=30)
        key = base64.b64encode(os.urandom(16)).decode()
        self._sock.sendall((
            f"GET /websocket HTTP/1.1\r\nHost: {host}:{port}\r\nUpgrade: websocket\r\n"
            f"Connection: Upgrade\r\nSec-WebSocket-Key: {key}\r\n"
            "Sec-WebSocket-Version: 13\r\n\r\n"
        ).encode())
        self._buf = b""
        while b"\r\n\r\n" not in self._buf:
            self._buf += self._recv()
        head, self._buf = self._buf.split(b"\r\n\r\n", 1)
        if b" 101 " not in head.split(b"\r\n", 1)[0]:
            raise ConnectionError(f"websocket upgrade refused: {head[:80]!r}")
        self._send_text(json.dumps({"subscribe": "Tx"}).encode())
        ack = json.loads(self._frame()[1])
        if ack.get("subscribed") != "Tx":
            raise ConnectionError(f"subscription refused: {ack}")
        self._sock.settimeout(None)

    def _recv(self) -> bytes:
        chunk = self._sock.recv(65536)
        if not chunk:
            raise ConnectionError("websocket closed")
        return chunk

    def _take(self, n: int) -> bytes:
        while len(self._buf) < n:
            self._buf += self._recv()
        out, self._buf = self._buf[:n], self._buf[n:]
        return out

    def _frame(self) -> tuple[int, bytes]:
        b0, b1 = self._take(2)
        n = b1 & 0x7F
        if n == 126:
            (n,) = struct.unpack(">H", self._take(2))
        elif n == 127:
            (n,) = struct.unpack(">Q", self._take(8))
        return b0 & 0x0F, self._take(n)

    def _send_text(self, payload: bytes, opcode: int = 1) -> None:
        mask = os.urandom(4)
        n = len(payload)
        head = bytes([0x80 | opcode]) + (
            bytes([0x80 | n]) if n < 126 else bytes([0x80 | 126]) + struct.pack(">H", n)
        )
        self._sock.sendall(head + mask + bytes(c ^ mask[i % 4] for i, c in enumerate(payload)))

    def run(self) -> None:
        try:
            while True:
                opcode, data = self._frame()
                now = time.monotonic_ns()
                if opcode == 8:
                    return
                if opcode == 1:
                    ev = json.loads(data)
                    if ev.get("type") == "Tx":
                        self.seen.setdefault(ev["hash"], (now, int(ev.get("code", -1))))
        except (OSError, ConnectionError, ValueError) as e:
            self.error = repr(e)

    def close(self) -> None:
        try:
            self._send_text(b"", opcode=8)
        except OSError:
            pass
        self._sock.close()


def _sender(k: int, job: dict, out: dict) -> None:
    conn = http.client.HTTPConnection(job["host"], job["port"], timeout=30)
    tag = job["tag"].encode()
    offsets_ns = job["offsets_ns"]
    try:
        for i in range(k, len(offsets_ns), job["senders"]):
            due = job["t0_ns"] + offsets_ns[i]
            sleep_until(due)
            tx = corpus_mod.make_tx(tag, job["first_tx"] + i, job["tx_bytes"])
            sent = time.monotonic_ns()
            try:
                conn.request("POST", "/broadcast_tx?tx=0x" + tx.hex())
                resp = conn.getresponse()
                body = json.loads(resp.read())
                res = body.get("result") or {}
                ok = resp.status == 200 and res.get("code") == 0 and not res.get("duplicate")
                out["status"][i] = 0 if ok else (resp.status if resp.status != 200 else -1)
            except (OSError, http.client.HTTPException, ValueError):
                out["status"][i] = -2
                conn.close()
                conn = http.client.HTTPConnection(job["host"], job["port"], timeout=30)
            out["sent_ns"][i] = sent
            out["acked_ns"][i] = time.monotonic_ns()
    finally:
        conn.close()


def run(job: dict) -> dict:
    """job: host, port, t0_ns, offsets_ns (one a tx), first_tx, tx_bytes, tag,
    senders, wait_s. Returns per tx: when it was sent and acknowledged, how the
    node answered, and when its commit event arrived (0 = never)."""
    import hashlib

    n = len(job["offsets_ns"])
    listener = EventListener(job["host"], job["port"])
    listener.start()
    out = {"status": [-3] * n, "sent_ns": [0] * n, "acked_ns": [0] * n}
    threads = [
        threading.Thread(target=_sender, args=(k, job, out), name=f"sender-{k}")
        for k in range(job["senders"])
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    hashes = [
        hashlib.sha256(
            corpus_mod.make_tx(job["tag"].encode(), job["first_tx"] + i, job["tx_bytes"])
        )
        .hexdigest().upper()
        for i in range(n)
    ]
    deadline = time.monotonic() + job["wait_s"]
    while time.monotonic() < deadline and listener.error is None:
        # a tx the node refused has no commit to wait for
        if all(h in listener.seen for h, st in zip(hashes, out["status"]) if st == 0):
            break
        time.sleep(0.05)
    listener.close()
    out["event_ns"] = [listener.seen.get(h, (0, -1))[0] for h in hashes]
    out["event_code"] = [listener.seen.get(h, (0, -1))[1] for h in hashes]
    out["listener_error"] = listener.error
    return out


def main() -> int:
    job = json.load(sys.stdin)
    json.dump(run(job), sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
