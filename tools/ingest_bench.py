#!/usr/bin/env python3
"""Microbench of the vote pool's frame ingest (``TxVotePool.check_tx_many``).

CPU, shape only: host Python on whatever machine runs this, good for the
proportions between its own rows and between two trees, never a number
of the chip's host. It drives frames shaped as a benchmark cell's through
a pool sized as that cell's, with the pool held at a steady resident
count the way commits hold it (the oldest frames are purged as new ones
arrive, so the dedup set evicts), and prints thread CPU microseconds a
vote (``time.thread_time()`` around each call, what the pool's own
``txvote_ingest_cpu_s`` counts):

  cold      votes as the harness hands them over: no cached bytes
  decoded   votes as the wire decoder leaves them: wire form, nothing else
  primed    wire form, gossip segment and vote key all set
  ... gc off  cold and primed again with the collector disabled: the
            difference to the row above is what the ingest's own
            allocations cost in young collections

and the tracked objects a resident vote adds besides its ``TxVote``.

    python tools/ingest_bench.py                    # val64-flood's frames
    python tools/ingest_bench.py --shape val4-flood
    PYTHONPATH=/path/to/another/tree python tools/ingest_bench.py
"""

from __future__ import annotations

import argparse
import gc
import hashlib
import json
import os
import sys
import time

# after PYTHONPATH, so that another tree named there is the one measured
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from txflow_tpu.codec import amino  # noqa: E402
from txflow_tpu.pool import TxInfo, TxVotePool  # noqa: E402
from txflow_tpu.types import TxVote  # noqa: E402
from txflow_tpu.types.tx_vote import encode_tx_vote  # noqa: E402
from txflow_tpu.utils.config import MempoolConfig  # noqa: E402

# frame = votes a call, validators = frames a chunk of txs, pool / dedup
# as perfbench/traffic/<traffic>.json or perfbench/cells/<cell>.json set them
SHAPES = {
    "val64-flood": {"frame": 256, "validators": 64, "pool": 557_056, "dedup": 1_114_112},
    "val4-flood": {"frame": 256, "validators": 4, "pool": 34_816, "dedup": 69_632},
    "val64-served": {"frame": 63, "validators": 1, "pool": 65_536, "dedup": 10_000},
}
TS_BASE_NS = 1_700_000_000_123_456_789
_oset = object.__setattr__


def _vote(tx_hash, tx_key, timestamp_ns, addr, sig) -> TxVote:
    """A signed vote with empty caches, built as the harness builds one."""
    v = TxVote.__new__(TxVote)
    _oset(v, "height", 0)
    _oset(v, "tx_hash", tx_hash)
    _oset(v, "tx_key", tx_key)
    _oset(v, "timestamp_ns", timestamp_ns)
    _oset(v, "validator_address", addr)
    _oset(v, "signature", sig)
    _oset(v, "_sb_cache", None)
    _oset(v, "_wire_cache", None)
    _oset(v, "_vk_cache", None)
    _oset(v, "_seg_cache", None)
    return v


def frames(shape: dict, n_frames: int, mode: str, salt: int):
    """n_frames frames: validator v's votes on a chunk of ``frame`` txs,
    the chunk's validators in order, then the next chunk."""
    per, n_vals = shape["frame"], shape["validators"]
    out = []
    for f in range(n_frames):
        chunk, v = divmod(f, n_vals)
        addr = hashlib.sha256(b"addr-%d" % v).digest()[:20]
        votes = []
        for i in range(chunk * per, chunk * per + per):
            key = hashlib.sha256(b"tx-%d-%d" % (salt, i)).digest()
            sig = hashlib.sha512(b"sig-%d-%d-%d" % (salt, i, v)).digest()
            vote = _vote(key.hex().upper(), key, TS_BASE_NS + i * n_vals + v, addr, sig)
            if mode != "cold":
                wire = encode_tx_vote(vote)  # primes _wire_cache
                if mode == "primed":
                    vote.vote_key()
                    _oset(vote, "_seg_cache", amino.length_prefixed(wire))
            votes.append(vote)
        out.append(votes)
    return out


def run(shape: dict, resident: int, measured: int, mode: str, salt: int) -> float:
    """us of thread CPU a vote over ``measured`` votes, after ``resident``
    votes fill the pool; the pool stays at ``resident``."""
    per = shape["frame"]
    pool = TxVotePool(MempoolConfig(size=shape["pool"], cache_size=shape["dedup"],
                                    max_txs_bytes=1 << 40))
    info = TxInfo(3)
    fill, meas = resident // per, measured // per
    fs = frames(shape, fill + meas, mode, salt)
    for votes in fs[:fill]:
        assert not any(pool.check_tx_many(votes, info))
    cpu = 0.0
    for j in range(fill, fill + meas):
        votes = fs[j]
        t0 = time.thread_time()
        errs = pool.check_tx_many(votes, info)
        cpu += time.thread_time() - t0
        assert not any(errs)
        pool.remove([v.vote_key() for v in fs[j - fill]])  # the commit's purge
    return cpu / (meas * per) * 1e6


def tracked_per_vote(shape: dict, n: int) -> float:
    """Tracked objects a resident vote adds besides its TxVote: the votes
    are built (and the collector run and frozen) first, so what
    ``gc.get_objects()`` gains is the pool's."""
    per = shape["frame"]
    pool = TxVotePool(MempoolConfig(size=max(shape["pool"], n), cache_size=2 * n,
                                    max_txs_bytes=1 << 40))
    fs = frames(shape, n // per, "cold", 99)
    gc.collect()
    gc.freeze()
    was = gc.isenabled()
    gc.disable()
    try:
        before = len(gc.get_objects())
        for votes in fs:
            pool.check_tx_many(votes, TxInfo(3))
        after = len(gc.get_objects())
    finally:
        if was:
            gc.enable()
        gc.unfreeze()
    return (after - before) / (len(fs) * per)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", choices=sorted(SHAPES), default="val64-flood")
    ap.add_argument("--resident", type=int, default=None,
                    help="votes held in the pool while measuring "
                         "(default: 65,536, or half the shape's pool if that is less)")
    ap.add_argument("--votes", type=int, default=131_072, help="votes measured a row")
    ap.add_argument("--repeat", type=int, default=3)
    args = ap.parse_args()
    shape = SHAPES[args.shape]
    if args.resident is None:
        args.resident = min(65_536, shape["pool"] // 2) // shape["frame"] * shape["frame"]
    rows: dict[str, list[float]] = {}
    for rep in range(args.repeat):
        for mode in ("cold", "decoded", "primed"):
            rows.setdefault(mode, []).append(
                run(shape, args.resident, args.votes, mode, rep))
        gc.disable()
        try:
            for mode in ("cold", "primed"):
                rows.setdefault(mode + " gc off", []).append(
                    run(shape, args.resident, args.votes, mode, rep))
                gc.collect()
        finally:
            gc.enable()
    print(f"CPU, shape only: {args.shape} frames of {shape['frame']}, "
          f"{args.resident} resident, {args.votes} votes a row, thread CPU us a vote")
    for mode, xs in rows.items():
        print(f"  {mode:14s} " + " ".join(f"{x:6.2f}" for x in xs) + f"   least {min(xs):.2f}")
    tracked = tracked_per_vote(shape, 16_384)
    print(f"  tracked objects a resident vote besides the TxVote: {tracked:.3f}")
    print(json.dumps({"shape": args.shape, "cpu_shape_only": True,
                      "us_per_vote": {m: min(x) for m, x in rows.items()},
                      "tracked_objects_per_vote": tracked}))


if __name__ == "__main__":
    main()
