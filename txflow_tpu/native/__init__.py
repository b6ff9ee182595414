"""Native host-runtime pieces, built on demand with the system C compiler.

``prep.c`` implements the batched verify prep (SHA-512 + mod-L + ScMinimal)
that feeds the device kernel; the Python fallback in ops/ed25519_batch.py
remains both the parity oracle and the no-compiler path. The library is
(re)built lazily the first time it is needed — one ``cc -O3 -shared`` per
source change, cached as ``_prep.so`` next to the source.

No pip/apt dependencies: plain ctypes against a cc-built shared object.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "prep.c")
_SRC_CODEC = os.path.join(_DIR, "codec.c")
_SO = os.path.join(_DIR, "_prep.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _build(force: bool = False) -> bool:
    """Compile prep.c -> _prep.so if missing or stale (or ``force``: the
    library that lies there is not trusted). True on success."""
    # codec.c is optional: a tree without it still builds prep.c alone
    # (sign_bytes_batch then reports unavailable via the hasattr check)
    srcs = [s for s in (_SRC, _SRC_CODEC) if os.path.exists(s)]
    if not srcs:
        return False
    try:
        if (
            not force
            and os.path.exists(_SO)
            and os.path.getmtime(_SO) >= max(os.path.getmtime(s) for s in srcs)
        ):
            return True
    except OSError:
        return False
    tmp = _SO + ".tmp%d" % os.getpid()
    for cc in ("cc", "gcc", "g++"):
        try:
            r = subprocess.run(
                [cc, "-O3", "-shared", "-fPIC", "-o", tmp] + srcs,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            os.replace(tmp, _SO)  # atomic vs concurrent builders
            return True
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return False


def _load(rebuild: bool = False):
    global _lib, _tried
    with _lock:
        if _tried and not rebuild:
            return _lib
        _tried = True
        _lib = None
        if not _build(force=rebuild):
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.txflow_prep_batch.argtypes = [
            u8p, i64p, u8p, u8p, u8p, ctypes.c_int64, u8p, u8p, u8p,
        ]
        lib.txflow_prep_batch.restype = None
        lib.txflow_sha512.argtypes = [u8p, ctypes.c_size_t, u8p]
        lib.txflow_sha512.restype = None
        i32p = ctypes.POINTER(ctypes.c_int32)
        # codec.c symbols are OPTIONAL (the .so may have been built
        # without it): ctypes attribute access raises on a missing
        # symbol, which would otherwise break available() entirely and
        # make the hasattr fallbacks downstream unreachable (r5 review)
        try:
            lib.txflow_sign_bytes_batch.argtypes = [
                ctypes.c_int64,  # n_votes
                i64p,  # heights
                u8p, ctypes.c_int64, i32p,  # hashes, stride, lens
                i64p,  # timestamps
                u8p, ctypes.c_int32,  # chain, len
                u8p, ctypes.c_int64, i32p,  # out, stride, lens
            ]
            lib.txflow_sign_bytes_batch.restype = None
            lib.txflow_decode_votes.argtypes = [
                u8p, i64p, ctypes.c_int64,  # buf, offsets, n
                i64p, i64p,  # heights, timestamps
                i32p, i32p,  # hash off/len
                i32p,  # key off
                i32p, i32p,  # addr off/len
                i32p, i32p,  # sig off/len
                u8p,  # flags
            ]
            lib.txflow_decode_votes.restype = None
        except AttributeError:
            pass
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def rebuild() -> None:
    """Build ``_prep.so`` from ``prep.c`` + ``codec.c`` NOW and load it.

    ``_build`` trusts a library that lies next to the sources by mtime,
    which a copy of the tree need not keep — and git ignores the library,
    so one that is there was built by some other run from some other
    sources. A caller that must know what serves (chip_smoke.py) calls
    this first: the sources git tracks are compiled over whatever lies
    there (atomically — a concurrent loader sees the old file or the new
    one), and a failure RAISES instead of dropping to the numpy path. The
    node itself keeps the lazy build and the numpy fallback."""
    lib = _load(rebuild=True)
    if lib is None:
        raise RuntimeError(
            f"native prep build failed: no C compiler produced {_SO} "
            f"from {_SRC} and {_SRC_CODEC}"
        )
    if not hasattr(lib, "txflow_sign_bytes_batch"):
        raise RuntimeError(f"{_SO} was built without {_SRC_CODEC}")


def serving() -> str:
    """Which host-prep path this process runs: ``"native"`` once the C
    library is loaded, else ``"numpy"``."""
    return "native" if available() else "numpy"


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def sha512(data: bytes) -> bytes:
    """One-shot SHA-512 through the native module (parity-test surface)."""
    lib = _load()
    assert lib is not None
    buf = np.frombuffer(data, np.uint8) if data else np.zeros(0, np.uint8)
    out = np.zeros(64, np.uint8)
    lib.txflow_sha512(_u8p(np.ascontiguousarray(buf)), len(data), _u8p(out))
    return out.tobytes()


def prep_batch(
    msgs_concat: np.ndarray,
    offsets: np.ndarray,
    sigs: np.ndarray,
    pubs: np.ndarray,
    ok_in: np.ndarray,
):
    """Batched S/h prep: returns (s_le [n,32], h_le [n,32], ok [n] bool).

    msgs_concat: uint8[total]; offsets: int64[n+1]; sigs: uint8[n,64];
    pubs: uint8[n,32] (pre-gathered per vote); ok_in: uint8[n] (host checks:
    signature length, validator index range, key decompresses).
    """
    lib = _load()
    assert lib is not None
    n = len(ok_in)
    s_le = np.zeros((n, 32), np.uint8)
    h_le = np.zeros((n, 32), np.uint8)
    ok = np.zeros(n, np.uint8)
    lib.txflow_prep_batch(
        _u8p(np.ascontiguousarray(msgs_concat)),
        np.ascontiguousarray(offsets, np.int64).ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64)
        ),
        _u8p(np.ascontiguousarray(sigs)),
        _u8p(np.ascontiguousarray(pubs)),
        _u8p(np.ascontiguousarray(ok_in, np.uint8)),
        n,
        _u8p(s_le),
        _u8p(h_le),
        _u8p(ok),
    )
    return s_le, h_le, ok.astype(bool)


def sign_bytes_batch(
    heights: list[int],
    tx_hashes: list[str],
    timestamps: list[int],
    chain_id: str,
) -> list[bytes | None] | None:
    """Batched canonical sign bytes (codec.c).

    None when the native library is unavailable; otherwise a per-vote
    list where an item is None if its fields exceed the C-side bounds
    (hash > 256 chars / chain id > 128 bytes — possible only for hostile
    votes; real hashes are 64 chars). Callers Python-fallback per item.
    """
    lib = _load()
    if lib is None or not hasattr(lib, "txflow_sign_bytes_batch"):
        return None
    n = len(heights)
    if n == 0:
        return []
    chain = chain_id.encode()
    hb = [h.encode() for h in tx_hashes]
    hash_stride = max(len(b) for b in hb) or 1
    hashes = np.zeros((n, hash_stride), np.uint8)
    hash_lens = np.zeros(n, np.int32)
    for i, b in enumerate(hb):
        hashes[i, : len(b)] = np.frombuffer(b, np.uint8)
        hash_lens[i] = len(b)
    out_stride = 96 + hash_stride + len(chain)
    out = np.zeros((n, out_stride), np.uint8)
    out_lens = np.zeros(n, np.int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.txflow_sign_bytes_batch(
        n,
        np.ascontiguousarray(heights, np.int64).ctypes.data_as(i64p),
        _u8p(hashes), hash_stride, hash_lens.ctypes.data_as(i32p),
        np.ascontiguousarray(timestamps, np.int64).ctypes.data_as(i64p),
        _u8p(np.frombuffer(chain, np.uint8)) if chain else _u8p(np.zeros(1, np.uint8)),
        len(chain),
        _u8p(out), out_stride, out_lens.ctypes.data_as(i32p),
    )
    ob = out.tobytes()
    # per-item None for oversized fields (the C side hard-rejects them —
    # a hostile vote must only cost ITS OWN Python fallback, not the
    # whole batch's)
    return [
        ob[i * out_stride : i * out_stride + out_lens[i]]
        if out_lens[i] >= 0
        else None
        for i in range(n)
    ]


def decode_votes_fields(segs: list[bytes]):
    """Batch field-location pass for amino TxVote segments (codec.c).

    Returns (heights, timestamps, hash_off, hash_len, key_off, addr_off,
    addr_len, sig_off, sig_len, flags, concat) — offsets into ``concat``;
    flags bit0 = parsed ok, bit1 = canonical wire, bit2 = exactness
    corner needing the Python decoder. None when native is unavailable.
    The caller (types.tx_vote.decode_tx_votes_many) slices fields and
    builds the TxVote objects.
    """
    lib = _load()
    if lib is None or not hasattr(lib, "txflow_decode_votes"):
        return None
    n = len(segs)
    concat = b"".join(segs)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(s) for s in segs], out=offsets[1:])
    buf = (
        np.frombuffer(concat, np.uint8)
        if concat
        else np.zeros(1, np.uint8)
    )
    heights = np.zeros(n, np.int64)
    timestamps = np.zeros(n, np.int64)
    i32 = lambda: np.zeros(n, np.int32)  # noqa: E731
    hash_off, hash_len = i32(), i32()
    key_off = i32()
    addr_off, addr_len = i32(), i32()
    sig_off, sig_len = i32(), i32()
    flags = np.zeros(n, np.uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.txflow_decode_votes(
        _u8p(buf),
        offsets.ctypes.data_as(i64p),
        n,
        heights.ctypes.data_as(i64p),
        timestamps.ctypes.data_as(i64p),
        hash_off.ctypes.data_as(i32p), hash_len.ctypes.data_as(i32p),
        key_off.ctypes.data_as(i32p),
        addr_off.ctypes.data_as(i32p), addr_len.ctypes.data_as(i32p),
        sig_off.ctypes.data_as(i32p), sig_len.ctypes.data_as(i32p),
        _u8p(flags),
    )
    return (
        heights, timestamps, hash_off, hash_len, key_off,
        addr_off, addr_len, sig_off, sig_len, flags, concat,
    )
