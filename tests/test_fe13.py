"""Radix-2^13 field arithmetic parity (fe13) + end-to-end kernel parity
under TXFLOW_FE_RADIX=13.

The fe13 module is the 20-limb upgrade of ops/fe.py; every op must agree
with python-int ground truth on random and adversarial values, and the
full verify kernel must reproduce the radix-8 accept/reject decisions
bit-for-bit (the radix is an internal representation choice — Go's
crypto/ed25519 semantics, types/tx_vote.go:110-119, cannot depend on it).
"""

import conftest  # noqa: F401

import hashlib
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from txflow_tpu.ops import fe13

P = fe13.P_INT


def rnd_ints(n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        out.append(int.from_bytes(rng.bytes(32), "little") % P)
    return out


def test_limb_roundtrip_and_bytes():
    vals = rnd_ints(20, 1) + [0, 1, 19, P - 1, 2**255 - 20]
    for v in vals:
        limbs = fe13.int_to_limbs(v)
        assert limbs.shape == (fe13.NLIMB,)
        assert (limbs >= 0).all() and (limbs <= fe13.MASK).all()
        assert fe13.limbs_to_int(limbs) == v
        b = (v % 2**256).to_bytes(32, "little")
        assert fe13.limbs_to_int(fe13.bytes_to_limbs(b)) == v


def test_bytes_to_limbs_device_matches_host():
    rng = np.random.default_rng(2)
    raw = rng.integers(0, 256, size=(64, 32), dtype=np.uint8)
    dev = np.asarray(fe13.bytes_to_limbs_device(jnp.asarray(raw)))
    for i in range(raw.shape[0]):
        host = fe13.bytes_to_limbs(raw[i].tobytes())
        np.testing.assert_array_equal(dev[i], host)


def _as_batch(vals):
    return jnp.asarray(np.stack([fe13.int_to_limbs(v) for v in vals]))


def test_mul_add_sub_parity():
    a_vals = rnd_ints(50, 3)
    b_vals = rnd_ints(50, 4)
    a, b = _as_batch(a_vals), _as_batch(b_vals)
    mul = fe13.fe_mul(a, b)
    add = fe13.fe_add(a, b)
    sub = fe13.fe_sub(a, b)
    for i, (x, y) in enumerate(zip(a_vals, b_vals)):
        assert fe13.limbs_to_int(mul[i]) % P == (x * y) % P
        assert fe13.limbs_to_int(add[i]) % P == (x + y) % P
        assert fe13.limbs_to_int(sub[i]) % P == (x - y) % P


def test_mul_bounds_after_add_chain():
    """The documented normalized bound: outputs of add/sub/mul chains stay
    legal fe_mul inputs (limbs <= ~9408) and results stay exact."""
    a_vals = rnd_ints(16, 5)
    b_vals = rnd_ints(16, 6)
    a, b = _as_batch(a_vals), _as_batch(b_vals)
    s = fe13.fe_add(a, b)           # carried sum
    d = fe13.fe_sub(s, b)           # back to a (mod p)
    m = fe13.fe_mul(s, d)
    assert int(np.asarray(s).max()) <= 9408
    assert int(np.asarray(d).max()) <= 9408
    assert int(np.asarray(m).max()) <= 9408
    for i, (x, y) in enumerate(zip(a_vals, b_vals)):
        assert fe13.limbs_to_int(m[i]) % P == ((x + y) * x) % P


def test_freeze_and_inv_parity():
    vals = rnd_ints(24, 7) + [0, 1, P - 1, 19]
    x = _as_batch(vals)
    sq = fe13.fe_sq(x)
    frozen = fe13.fe_freeze(sq)
    fr = np.asarray(frozen)
    for i, v in enumerate(vals):
        got = fe13.limbs_to_int(fr[i])
        assert got == (v * v) % P  # frozen = canonical, no mod needed
        assert (fr[i] >= 0).all() and (fr[i] <= fe13.MASK).all()
    nz = [v for v in vals if v != 0]
    inv = fe13.fe_inv(_as_batch(nz))
    for i, v in enumerate(nz):
        assert (fe13.limbs_to_int(inv[i]) * v) % P == 1


def test_freeze_edge_values():
    """Values engineered to need both top-bit folds and both conditional
    p-subtractions."""
    edge = [P - 1, P, P + 1, 2 * P - 1, 2**255 - 1, 2**255, 19, 0]
    # feed them in UNREDUCED limb form (value possibly >= p)
    x = jnp.asarray(
        np.stack([
            np.array(
                [(v >> (13 * i)) & fe13.MASK for i in range(fe13.NLIMB)],
                dtype=np.int32,
            )
            for v in edge
        ])
    )
    fr = np.asarray(fe13.fe_freeze(x))
    for i, v in enumerate(edge):
        assert fe13.limbs_to_int(fr[i]) == v % P


def test_full_kernel_parity_radix13():
    """End-to-end: the verify kernel under TXFLOW_FE_RADIX=13 reproduces
    the host verifier's accept/reject decisions on an adversarial batch
    (run in a subprocess — the radix is an import-time choice)."""
    code = r"""
import os
os.environ["TXFLOW_FE_RADIX"] = "13"
os.environ["JAX_PLATFORMS"] = "cpu"
import hashlib
import numpy as np
from txflow_tpu.crypto import ed25519 as host_ed
from txflow_tpu.ops import fe, ed25519_batch

assert fe.NLIMB == 20 and fe.RADIX == 13, "radix switch did not engage"

seeds = [hashlib.sha256(b"r13-%d" % i).digest() for i in range(4)]
pubs = [host_ed.public_key_from_seed(s) for s in seeds]
epoch = ed25519_batch.EpochTables(pubs)
assert epoch.tables.shape[-1] == 20

msgs, sigs, vidx, expect = [], [], [], []
for t in range(24):
    msg = b"radix13-parity-%d" % t
    vi = t % 4
    sig = host_ed.sign(seeds[vi], msg)
    mode = t % 4
    if mode == 1:
        sig = sig[:8] + bytes([sig[8] ^ 1]) + sig[9:]   # corrupt R
    elif mode == 2:
        sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]  # corrupt S
    elif mode == 3 and t % 8 == 7:
        vi = (vi + 1) % 4  # wrong key
    msgs.append(msg); sigs.append(sig); vidx.append(vi)
    expect.append(host_ed.verify(pubs[vi], msg, sig))

batch = ed25519_batch.prepare_batch(msgs, sigs, np.array(vidx), epoch)
got = ed25519_batch.verify_batch(batch)
assert list(got) == expect, (list(got), expect)

# compact/gather path too
import jax.numpy as jnp
cb = ed25519_batch.prepare_compact(msgs, sigs, np.array(vidx), epoch)
got2 = np.asarray(ed25519_batch.verify_kernel_gather(
    jnp.asarray(cb.s_nibbles), jnp.asarray(cb.h_nibbles),
    jnp.asarray(cb.val_idx.astype(np.int32)), jnp.asarray(epoch.tables),
    jnp.asarray(cb.r_y), jnp.asarray(cb.r_sign), jnp.asarray(cb.pre_ok)))
assert list(got2) == expect, (list(got2), expect)
print("RADIX13 KERNEL PARITY OK")
"""
    env = dict(os.environ)
    env["TXFLOW_FE_RADIX"] = "13"
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(parts + [repo])
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=repo,
        env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RADIX13 KERNEL PARITY OK" in r.stdout


@pytest.mark.slow  # 8-way mesh compile of the radix13 kernel: ~65s on 1-core CPU
def test_sharded_mesh_parity_radix13():
    """The 8-device shard_map verify+tally path under TXFLOW_FE_RADIX=13:
    decisions must match the scalar golden model (the radix swap must
    compose with the vote-axis sharding, psum tally included). Subprocess:
    the radix is an import-time choice."""
    code = r"""
import os
os.environ["TXFLOW_FE_RADIX"] = "13"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
import hashlib
import numpy as np
from txflow_tpu.crypto import ed25519 as host_ed
from txflow_tpu.ops import fe
from txflow_tpu.parallel import make_mesh
from txflow_tpu.types import TxVote, Validator, ValidatorSet, canonical_sign_bytes
from txflow_tpu.verifier import DeviceVoteVerifier, ScalarVoteVerifier

assert fe.NLIMB == 20

seeds = [hashlib.sha256(b"m13-%d" % i).digest() for i in range(4)]
pubs = [host_ed.public_key_from_seed(s) for s in seeds]
vals = ValidatorSet([Validator.from_pub_key(p, 10) for p in pubs])
seed_by_pub = dict(zip(pubs, seeds))
seeds_sorted = [seed_by_pub[v.pub_key] for v in vals]

msgs, sigs, vidx, slot = [], [], [], []
for t in range(4):
    h = hashlib.sha256(b"tx%d" % t).hexdigest().upper()
    for vi in range(4):
        m = canonical_sign_bytes("mesh13", 1, h, 1700000000_000000000 + t)
        s = host_ed.sign(seeds_sorted[vi], m)
        if (t * 4 + vi) % 5 == 3:
            s = s[:12] + bytes([s[12] ^ 1]) + s[13:]  # corrupt some
        msgs.append(m); sigs.append(s); vidx.append(vi); slot.append(t)

mesh = make_mesh(8)
dev = DeviceVoteVerifier(vals, mesh=mesh)
sca = ScalarVoteVerifier(vals)
rd = dev.verify_and_tally(msgs, sigs, np.array(vidx), np.array(slot), 4)
rs = sca.verify_and_tally(msgs, sigs, np.array(vidx), np.array(slot), 4)
np.testing.assert_array_equal(rd.valid, rs.valid)
np.testing.assert_array_equal(rd.stake.astype(np.int64), rs.stake)
np.testing.assert_array_equal(rd.maj23, rs.maj23)
print("MESH RADIX13 PARITY OK")
"""
    env = dict(os.environ)
    env["TXFLOW_FE_RADIX"] = "13"
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(parts + [repo])
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=repo,
        env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MESH RADIX13 PARITY OK" in r.stdout
