"""Simulate a device cost model on CPU (dev tool; UNCALIBRATED — its
defaults predate this tree's first chip run, see ROADMAP Queue 3 item 8).

Validates the shared-VerifyCache claim design against device economics
WITHOUT a device: every verify call pays a cost of the r5-measured
shape — a fixed per-call latency plus a per-PADDED-slot cost, padded on
the same miss-bucket ladder DeviceVoteVerifier derives from its engine
buckets — while verification itself is instant (signatures accepted for
known validators, like profile_host's instant verifier). The bill is
paid BETWEEN verify and store, exactly where real hardware pays it, so
deferred engines wait out the owner's device call before their retry
hits. One module-global lock serializes charges: one physical chip.

Run A: four engines share ONE cache with claims (the bench default).
Run B: no cache — each node pays the device for every vote (the honest
baseline config, and the reference's topology).

Measured-economics defaults: ~8 ms fixed per call; ~27.6 us per padded
slot at bucket 4096 (bench device-step 24,433 votes/s all-in).
r5 sim result (4096 txs, serialized device):
  shared-cache+claims  ~22.2k votes/s  (host-bound: device busy 1.0 s
                        of 2.2 s wall; 30.7k padded slots for 16.4k
                        unique votes)
  no-cache             ~10.4k votes/s  (device-bound: 4.4 s busy of
                        4.7 s wall; 154.6k padded slots = 4x redundancy
                        x padding).

Usage: JAX_PLATFORMS=cpu python tools/sim_device.py [--fixed-ms 8]
       [--per-slot-us 27.6] [--txs 4096] [--mesh-devices 4] [--psum-ms 0.5]
       [--host-workers 4] [--host-us-per-vote 41] [--gil-frac 0.55]
       [--shm-ms 1.5]
With --mesh-devices N the per-slot bill divides across N chips (plus one
psum per step); the run ends with a host-vs-device crossover table showing
the mesh size past which HOST prep binds and worker scaling takes over,
then a thread-vs-process host-pool backend crossover (at which worker
count the process backend's GIL escape beats its shared-memory toll —
the --host-prep-backend advisor for bench.py).
"""

import argparse
import hashlib
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from txflow_tpu.node import LocalNet
from txflow_tpu.types import MockPV, TxVote, Validator, ValidatorSet
from txflow_tpu.utils.config import test_config
from txflow_tpu.verifier import (
    ScalarVoteVerifier,
    TallyResult,
    VerifyCache,
    bucket_size,
    first_occurrence_mask,
)

_DEVICE_LOCK = threading.Lock()


class SimDeviceVerifier(ScalarVoteVerifier):
    """Instant-accept verifier charging the device bill per call.

    Reimplements both verify paths (fused and cached) instead of
    patching the parent's crypto: validity is simply "known validator"
    (the sim's corpus is all-honest), and the cached path inserts the
    device charge between claim and store — the point where real
    hardware holds the claims while the kernel runs."""

    def __init__(self, val_set, shared_cache=None, fixed_s=0.008,
                 per_slot_s=27.6e-6, buckets=(4096, 16384),
                 mesh_devices=1, psum_s=0.0005):
        super().__init__(val_set, shared_cache=shared_cache)
        self._fixed_s = fixed_s
        self._per_slot_s = per_slot_s
        # N-way vote-sharded mesh: per-slot work divides across devices,
        # plus ONE stake psum per step (parallel.mesh ring/psum combine —
        # a single small collective regardless of batch size)
        self._mesh = max(1, int(mesh_devices))
        self._psum_s = psum_s if self._mesh > 1 else 0.0
        self.buckets = buckets
        # the device's own miss ladder derivation (verifier.py
        # DeviceVoteVerifier.__init__) — bench pair (4096, 16384)
        # yields (256, 1024, 4096, 16384)
        self.miss_buckets = tuple(
            sorted(
                {max(64, b // 16) for b in buckets}
                | {max(64, b // 4) for b in buckets}
                | set(buckets)
            )
        )
        self.device_calls = 0
        self.device_slots = 0
        self.device_busy_s = 0.0

    def _charge(self, n: int, ladder) -> None:
        if n == 0:
            return
        # mesh shards pad to per-device divisibility, same as
        # DeviceVoteVerifier (bucket_size multiple=_n_shards)
        b = bucket_size(n, ladder, multiple=self._mesh)
        cost = self._fixed_s + self._psum_s + b * self._per_slot_s / self._mesh
        # one physical chip (or slice): concurrent callers serialize;
        # counters are shared across engine threads, so they mutate
        # under the lock
        with _DEVICE_LOCK:
            self.device_calls += 1
            self.device_slots += b
            self.device_busy_s += cost
            time.sleep(cost)

    def _validity(self, val_idx, keep) -> np.ndarray:
        n_vals = len(self._pub_keys)
        return keep & (val_idx >= 0) & (val_idx < n_vals)

    def verify_and_tally(self, msgs, sigs, val_idx, tx_slot, n_slots,
                         prior_stake=None, quorum=None):
        n = len(msgs)
        val_idx = np.asarray(val_idx, dtype=np.int64)
        tx_slot = np.asarray(tx_slot, dtype=np.int64)
        keep = first_occurrence_mask(tx_slot, val_idx)
        pending = np.zeros(n, dtype=bool)
        if self.cache is None:
            # fused path: the whole batch pads to the engine bucket
            self._charge(n, self.buckets)
            valid = self._validity(val_idx, keep)
        else:
            n_vals = len(self._pub_keys)
            keys = [
                VerifyCache.key(msgs[i], sigs[i], self._pub_keys[int(val_idx[i])])
                if keep[i] and 0 <= val_idx[i] < n_vals
                else None
                for i in range(n)
            ]
            cached, pending = self.cache.lookup_or_claim_many(keys)
            valid = np.zeros(n, dtype=bool)
            owned = []
            for i in range(n):
                if keys[i] is None or pending[i]:
                    continue
                if cached[i] is not None:
                    valid[i] = cached[i]
                else:
                    owned.append(i)
            if owned:
                verdicts = self._validity(
                    val_idx[owned], np.ones(len(owned), dtype=bool)
                )
                # the device runs HERE, claims held; deferred engines
                # cannot hit until the store below
                self._charge(len(owned), self.miss_buckets)
                self.cache.store_many(
                    [(keys[i], bool(v)) for i, v in zip(owned, verdicts)]
                )
                valid[owned] = verdicts
        stake = (
            np.zeros(n_slots, dtype=np.int64)
            if prior_stake is None
            else np.asarray(prior_stake, dtype=np.int64).copy()
        )
        ok = valid & (tx_slot >= 0) & (tx_slot < n_slots)
        np.add.at(stake, tx_slot[ok], self._powers[val_idx[ok]].astype(np.int64))
        q = self.val_set.quorum_power() if quorum is None else quorum
        return TallyResult(valid, stake, stake >= q, ~keep | pending)


def run(shared: bool, n_txs: int, fixed_s: float, per_slot_s: float,
        mesh_devices: int = 1, psum_s: float = 0.0005) -> dict:
    n_vals = 4
    pvs = [MockPV(hashlib.sha256(b"sim%d" % i).digest()) for i in range(n_vals)]
    by_addr = {pv.get_address(): pv for pv in pvs}
    val_set = ValidatorSet(
        [Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs]
    )
    pvs = [by_addr[v.address] for v in val_set.validators]
    cfg = test_config()
    cfg.mempool.size = 16 * n_txs * (n_vals + 1)
    cfg.mempool.cache_size = 2 * cfg.mempool.size
    cfg.engine.min_batch = 3072
    cfg.engine.batch_wait = 0.05

    verifiers = []
    cache = VerifyCache() if shared else None

    def mk():
        v = SimDeviceVerifier(
            val_set, shared_cache=cache, fixed_s=fixed_s, per_slot_s=per_slot_s,
            mesh_devices=mesh_devices, psum_s=psum_s,
        )
        verifiers.append(v)
        return v

    if shared:
        net = LocalNet(4, chain_id="sim", config=cfg, use_device_verifier=False,
                       sign=False, mempool_broadcast=False, priv_vals=pvs,
                       verifier=mk(), index_txs=False)
    else:
        net = LocalNet(4, chain_id="sim", config=cfg, use_device_verifier=False,
                       sign=False, mempool_broadcast=False, priv_vals=pvs,
                       index_txs=False)
        for nd in net.nodes:  # per-node device bill, no cache
            nd.txflow.verifier = mk()

    txs = [b"sim%d=v" % i for i in range(n_txs)]
    votes_by_val = [[] for _ in range(n_vals)]
    for tx in txs:
        k = hashlib.sha256(tx).digest()
        for vi, pv in enumerate(pvs):
            v = TxVote(height=0, tx_hash=k.hex().upper(), tx_key=k,
                       validator_address=pv.get_address())
            pv.sign_tx_vote("sim", v)
            votes_by_val[vi].append(v)
    net.start()
    try:
        t0 = time.perf_counter()
        chunk = 2048
        for base in range(0, n_txs, chunk):
            tx_chunk = txs[base:base + chunk]
            for nd in net.nodes:
                nd.mempool.check_tx_many(tx_chunk)
            for vi, nd in enumerate(net.nodes):
                nd.tx_vote_pool.check_tx_many(votes_by_val[vi][base:base + chunk])
        ok = net.wait_all_committed(txs, timeout=600)
        wall = time.perf_counter() - t0
        committed = net.committed_votes_total()
        assert ok, "sim run timed out"
    finally:
        net.stop()
    return {
        "votes_per_sec": round(committed / wall, 1),
        "wall_s": round(wall, 2),
        "device_calls": sum(v.device_calls for v in verifiers),
        "device_slots": sum(v.device_slots for v in verifiers),
        "device_busy_s": round(sum(v.device_busy_s for v in verifiers), 2),
    }


def print_crossover(fixed_s, psum_s, per_slot_s, host_us_per_vote,
                    host_workers, bucket=4096):
    """Host-vs-device crossover: on an N-way mesh the device step is
    fixed + psum + b*per_slot/N, but the HOST still preps every vote —
    b*host_us/W with a W-worker prep pool. Past the crossover mesh size,
    adding devices buys nothing; adding host workers does."""
    w = max(1, host_workers)
    host_s = bucket * host_us_per_vote / 1e6 / w
    print(f"host-vs-device crossover at bucket {bucket}, "
          f"{w} host worker(s) (host prep {host_s*1e3:.1f} ms/batch):")
    crossed = None
    for n in (1, 2, 4, 8, 16, 32, 64):
        dev_s = fixed_s + (psum_s if n > 1 else 0.0) + bucket * per_slot_s / n
        step_s = max(dev_s, host_s)
        bound = "host" if host_s > dev_s else "device"
        if crossed is None and host_s > dev_s:
            crossed = n
        print(f"  mesh={n:2d}  device {dev_s*1e3:7.1f} ms  "
              f"ceiling {bucket/step_s:9.0f} votes/s  bound={bound}")
    if crossed is None:
        print("  device-bound through mesh=64: more devices still pay off")
    else:
        print(f"  crossover at mesh={crossed}: host-bound beyond this — "
              f"scale host workers (--host-workers), not devices")


def backend_model(bucket: int, host_us_per_vote: float, workers: int,
                  gil_frac: float, shm_ms: float) -> dict:
    """Per-batch host-prep cost under each pool backend (seconds).

    Thread backend: Amdahl with a GIL-serialized fraction — the
    sign-bytes assembly and Python-level glue hold the GIL, so only
    ``1 - gil_frac`` of the per-vote work parallelizes across W threads
    (hashlib/numpy release the GIL; the bytes plumbing does not).
    Process backend: near-linear scaling (workers hold separate GILs)
    plus a fixed per-batch shared-memory toll — segment create/pack/
    attach/ack (engine.hostprep._run_typed), which threads never pay.
    The crossover: processes win once the GIL-serialized slice of a
    batch exceeds the shm toll."""
    w = max(1, workers)
    serial_s = bucket * host_us_per_vote / 1e6
    thread_s = serial_s * (gil_frac + (1.0 - gil_frac) / w)
    proc_s = serial_s / w + (shm_ms / 1e3 if w > 1 else 0.0)
    return {"thread_s": thread_s, "process_s": proc_s}


def print_backend_crossover(host_us_per_vote: float, gil_frac: float,
                            shm_ms: float, bucket: int = 4096) -> None:
    """Thread-vs-process host-pool crossover table: at which worker
    count (if any) does the process backend's GIL escape beat its
    shared-memory toll? Advises --host-prep-backend for bench.py runs
    on multi-core postures; on a 1-core box the table shows why the
    thread backend stays the right default."""
    print(f"host-pool backend crossover at bucket {bucket} "
          f"(gil_frac={gil_frac:.2f}, shm toll {shm_ms:.1f} ms/batch):")
    crossed = None
    for w in (1, 2, 4, 8, 16):
        m = backend_model(bucket, host_us_per_vote, w, gil_frac, shm_ms)
        best = "process" if m["process_s"] < m["thread_s"] else "thread"
        if crossed is None and best == "process":
            crossed = w
        print(f"  workers={w:2d}  thread {m['thread_s']*1e3:7.1f} ms  "
              f"process {m['process_s']*1e3:7.1f} ms  best={best}")
    if crossed is None:
        print("  thread-bound through 16 workers: the shm toll outweighs "
              "the GIL escape at this batch size — keep backend=thread")
    else:
        print(f"  crossover at workers={crossed}: run "
              f"--host-prep-backend process at or past this width")


def lane_latency_model(arrival_vps: float, linger_s: float, fixed_s: float,
                       per_slot_s: float, mesh: int = 1,
                       bucket_cap: int = 512) -> dict:
    """Predicted priority-lane commit p50 under a lane linger (ISSUE 12).

    A vote that lands on the lane waits out the residual linger (uniform
    arrival within the hold window: half the effective hold on average,
    full at worst), then rides one dispatch (fixed + batch*per_slot/mesh)
    and the readback/route tail folded into fixed_s. The effective hold
    ends EARLY when the backlog fills a bucket: at arrival rate a and
    linger L the coalesced batch is min(a*L, cap), so the hold is
    min(L, cap/a). Returns the predicted p50/p99 and the dispatch rate —
    the sweep printer uses it to find the linger sweet spot where the
    added hold stops buying batch occupancy."""
    a = max(arrival_vps, 1e-9)
    hold_s = min(linger_s, bucket_cap / a)
    batch = max(1.0, min(a * hold_s, float(bucket_cap)))
    dispatch_s = fixed_s + batch * per_slot_s / max(1, mesh)
    # mean residual hold for uniform arrivals = hold/2 (p50), ~full hold
    # for the unluckiest arrivals (p99 ≈ first-in vote)
    p50_s = hold_s / 2.0 + dispatch_s
    p99_s = hold_s + dispatch_s
    return {
        "linger_ms": round(linger_s * 1e3, 3),
        "batch": round(batch, 1),
        "dispatches_per_s": round(a / batch, 1),
        "p50_ms": round(p50_s * 1e3, 3),
        "p99_ms": round(p99_s * 1e3, 3),
    }


def print_lane_sweep(arrival_vps: float, fixed_s: float, per_slot_s: float,
                     mesh: int = 1, bucket_cap: int = 512) -> None:
    """Sweep the priority-lane linger over the tuning range and print
    the predicted p50 curve — the knob's sweet spot before a live
    bench.py --latency-slo run confirms it."""
    print(f"priority-lane linger sweep at {arrival_vps:,.0f} votes/s "
          f"(mesh={mesh}, bucket_cap={bucket_cap}):")
    best = None
    for ms in (0.25, 0.5, 1.0, 2.0, 4.0, 8.0):
        r = lane_latency_model(arrival_vps, ms / 1e3, fixed_s, per_slot_s,
                               mesh, bucket_cap)
        if best is None or r["p50_ms"] < best["p50_ms"]:
            best = r
        print(f"  linger={ms:5.2f} ms  batch={r['batch']:7.1f}  "
              f"dispatch/s={r['dispatches_per_s']:8.1f}  "
              f"p50={r['p50_ms']:7.2f} ms  p99={r['p99_ms']:7.2f} ms")
    print(f"  sweet spot: linger={best['linger_ms']} ms "
          f"(p50 {best['p50_ms']} ms)")


def _quorum_votes(n_validators: int) -> int:
    # equal-stake approximation of >2/3 quorum: smallest vote count
    # whose stake strictly exceeds 2/3 of total
    return (2 * n_validators) // 3 + 1


def committee_cert_model(n_validators: int, committee_size: int,
                         fixed_s: float, per_slot_s: float,
                         host_us_per_vote: float) -> dict:
    """Per-commit certificate cost at ``n_validators``, with and without
    per-epoch committee sampling (committee/).

    Full-flood: every validator signs, the certificate carries a >2/3
    quorum of the FULL set and re-verifies via the per-signature host
    loop — votes gossiped per tx, cert votes and verify cost all linear
    in validator count. Committee mode: only the sampled committee signs
    (cert votes = quorum of COMMITTEE), and the re-check is ONE batched
    device call (fixed + rung * per_slot) — flat in validator count.
    162 B/vote is the compact wire cost (32 msg-digest + 64 sig + 64
    point/scalar material + framing) the bench stamps as cert_bytes."""
    c = min(committee_size, n_validators) if committee_size > 0 else n_validators
    full_votes = _quorum_votes(n_validators)
    com_votes = _quorum_votes(c)
    rung = 1 << (max(com_votes, 8) - 1).bit_length()
    return {
        "validators": n_validators,
        "committee": c,
        "full_cert_votes": full_votes,
        "com_cert_votes": com_votes,
        "full_verify_ms": round(full_votes * host_us_per_vote / 1e3, 3),
        "com_verify_ms": round((fixed_s + rung * per_slot_s) * 1e3, 3),
        "full_gossip_votes_per_tx": n_validators,
        "com_gossip_votes_per_tx": c,
        "full_cert_kb": round(full_votes * 162 / 1024, 1),
        "com_cert_kb": round(com_votes * 162 / 1024, 1),
    }


def print_committee_sweep(fixed_s: float, per_slot_s: float,
                          host_us_per_vote: float,
                          sizes=(16, 32, 64)) -> None:
    """Certificate verify cost vs validator count at committee sizes
    16/32/64: where the one-batched-call committee re-check crosses
    below the full-flood per-signature loop, and how cert size / gossip
    fan-out scale. The 256-validator bench config pins the model's
    committee=32 column against a live run."""
    counts = (64, 128, 256, 512, 1024)
    print(f"committee cert model (fixed={fixed_s * 1e3:.1f} ms, "
          f"per_slot={per_slot_s * 1e6:.1f} us, "
          f"host={host_us_per_vote:.1f} us/vote):")
    hdr = "  validators  full-flood(ms/KB/votes)"
    for c in sizes:
        hdr += f"   c={c}(ms/KB)"
    print(hdr)
    crossover = {c: None for c in sizes}
    for n in counts:
        full = committee_cert_model(n, 0, fixed_s, per_slot_s,
                                    host_us_per_vote)
        row = (f"  {n:10d}  {full['full_verify_ms']:8.2f}/"
               f"{full['full_cert_kb']:5.1f}/{full['full_cert_votes']:4d}")
        for c in sizes:
            m = committee_cert_model(n, c, fixed_s, per_slot_s,
                                     host_us_per_vote)
            row += f"  {m['com_verify_ms']:7.2f}/{m['com_cert_kb']:4.1f}"
            if crossover[c] is None and m["com_verify_ms"] < m["full_verify_ms"]:
                crossover[c] = n
        print(row)
    for c in sizes:
        n = crossover[c]
        where = f"{n} validators" if n is not None else "beyond swept range"
        print(f"  crossover c={c}: committee batched verify beats "
              f"full-flood host loop from {where} "
              f"(committee cost flat, full-flood linear)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fixed-ms", type=float, default=8.0)
    ap.add_argument("--per-slot-us", type=float, default=27.6)
    ap.add_argument("--txs", type=int, default=4096)
    ap.add_argument("--mesh-devices", type=int, default=1,
                    help="model an N-way vote-sharded mesh (one psum/step)")
    ap.add_argument("--psum-ms", type=float, default=0.5,
                    help="per-step stake-psum cost when mesh > 1")
    ap.add_argument("--host-workers", type=int, default=1,
                    help="host-prep pool width for the crossover model")
    ap.add_argument("--host-us-per-vote", type=float, default=41.0,
                    help="host prep cost per vote (sign-bytes + compact prep; "
                         "~41 us/vote gives the ROADMAP's 18.4k host-bound)")
    ap.add_argument("--gil-frac", type=float, default=0.55,
                    help="GIL-serialized fraction of per-vote host prep for "
                         "the thread-backend model (bytes glue holds the "
                         "GIL; hashlib/numpy release it)")
    ap.add_argument("--shm-ms", type=float, default=1.5,
                    help="fixed per-batch shared-memory toll of the process "
                         "backend (segment create/pack/attach/ack)")
    ap.add_argument("--lane-sweep", action="store_true",
                    help="print the priority-lane linger sweep (predicted "
                         "p50 vs lane linger at --lane-arrival-vps)")
    ap.add_argument("--lane-arrival-vps", type=float, default=800.0,
                    help="priority-lane offered load for --lane-sweep")
    ap.add_argument("--lane-bucket-cap", type=int, default=512,
                    help="priority_bucket_cap for --lane-sweep")
    ap.add_argument("--committee-sweep", action="store_true",
                    help="print the committee certificate model: verify "
                         "cost / cert bytes / gossip fan-out vs validator "
                         "count at committee sizes 16/32/64, with the "
                         "crossover vs the full-flood host loop")
    args = ap.parse_args()
    if args.lane_sweep:
        print_lane_sweep(args.lane_arrival_vps, args.fixed_ms / 1e3,
                         args.per_slot_us / 1e6, args.mesh_devices,
                         args.lane_bucket_cap)
        return
    if args.committee_sweep:
        print_committee_sweep(args.fixed_ms / 1e3, args.per_slot_us / 1e6,
                              args.host_us_per_vote)
        return
    for shared in (True, False):
        r = run(shared, args.txs, args.fixed_ms / 1e3, args.per_slot_us / 1e6,
                args.mesh_devices, args.psum_ms / 1e3)
        label = "shared-cache+claims" if shared else "no-cache (honest baseline)"
        print(f"{label:28s} {r}")
    print_crossover(args.fixed_ms / 1e3, args.psum_ms / 1e3,
                    args.per_slot_us / 1e6, args.host_us_per_vote,
                    args.host_workers)
    print_backend_crossover(args.host_us_per_vote, args.gil_frac,
                            args.shm_ms)


if __name__ == "__main__":
    main()
