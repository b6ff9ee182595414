"""chip_smoke.py's phases, run small on the CPU.

The script itself refuses to run without a TPU (``main`` below); its
phases are plain functions that take their sizes, so the same checks run
here at the 64-vote rung with a few dozen transactions. What the chip
adds — real rungs, real compiles — is ``python chip_smoke.py`` through
the chip tool; what the chip's compiler says of the real shapes is
``tests/test_chip_compile.py``.
"""

import json
import os

import pytest

import chip_smoke
from txflow_tpu import native
from txflow_tpu.faults import FlakyVerifier
from txflow_tpu.node import LocalNet
from txflow_tpu.parallel.mesh import make_mesh
from txflow_tpu.utils import compile_cache
from txflow_tpu.utils.config import test_config as _test_config

SMALL = (64,)  # one rung: one compile, shared by every test below


def test_phase_a_device_matches_golden_model_small():
    out = chip_smoke.phase_golden(n_votes=64, buckets=SMALL)
    assert out["shape"] == [("fused", 64, 64)]
    # the batch carries all three kinds of bad vote and both quorum outcomes
    assert 0 < out["valid"] < 64 and out["dropped"] > 0
    assert 0 < out["maj23_slots"] < out["slots"]


def test_phase_b_served_path_small():
    out = chip_smoke.phase_served(
        n_txs=32, n_burst=16, buckets=SMALL, commit_timeout=120.0
    )
    assert out["paced"]["certificates"] == 4 * 32
    assert out["burst"]["certificates"] == 4 * 16
    assert out["warm_shapes"] == 1 and out["cold_shapes"] == []
    for part in ("paced", "burst"):
        assert sum(out[part]["dispatches"].values()) > 0
    assert out["device_failures"] == out["fallback_calls"] == out["demotions"] == 0
    assert out["admission_shed"] == 0 and out["compiles_in_traffic"] == 0


def test_burst_must_ride_the_top_rung():
    """With one rung every batch rides the top one; on the smoke's real
    ladder only a batch of more than 64 votes does."""
    small_only = {("fused", 64, 64): 9, ("fused", 4096, 64): 0}
    assert chip_smoke.on_top_rung(small_only, chip_smoke.BUCKETS) == 0
    assert chip_smoke.on_top_rung(small_only, SMALL) == 9
    both = {**small_only, ("fused", 4096, 64): 2, ("fused", 4096, 4096): 1}
    assert chip_smoke.on_top_rung(both, chip_smoke.BUCKETS) == 3


def test_coalescing_config_holds_steps_and_sizes_the_pools():
    """The burst's config differs from LocalNet's default only where it
    says: the three engine waits, and pools that hold the whole burst."""
    cfg = chip_smoke.coalescing_config(1024, 4)
    votes = 4 * 1024
    assert cfg.mempool.size >= votes and cfg.mempool.cache_size >= 2 * votes
    want = _test_config()
    want.mempool.size = cfg.mempool.size
    want.mempool.cache_size = cfg.mempool.cache_size
    want.engine.coalesce_linger = want.engine.poll_interval = chip_smoke.HOLD_S
    want.engine.idle_flush = 0.0
    assert cfg == want


def test_phase_b_fails_when_the_device_failed_once():
    """One injected device failure: the resilience policy retries and the
    net still commits everything — and the smoke must NOT call that a
    pass, because the chip did not do all of it cleanly."""
    with pytest.raises(chip_smoke.SmokeFailure, match="device_failures = 1"):
        chip_smoke.phase_served(
            n_txs=8,
            n_burst=4,
            buckets=SMALL,
            commit_timeout=120.0,
            # warmup goes straight to the inner verifier, so call 0 is
            # the first batch of traffic
            wrap_device=lambda dv: FlakyVerifier(dv, fail_calls={0}),
        )


def test_mesh_phase_small_on_four_virtual_devices():
    out = chip_smoke.phase_mesh(n_votes=64, buckets=SMALL, n_chips=4)
    assert out["chips"] == 4 and len(out["device_ids"]) == 4
    assert out["vote_rows_per_device"] == 16


def test_main_exits_nonzero_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    captured = capsys.readouterr()
    assert captured.out == "", "no phase may run and no result may print"
    assert "no TPU" in captured.err


def test_last_line_contract_is_exactly_the_device_object():
    """The driver reads the last line as one JSON object with these keys
    and nothing more."""
    line = chip_smoke.result_line("tpu", "TPU v5 lite", 1)
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert "\n" not in line


@pytest.mark.parametrize(
    "env, want",
    [
        ("/some/dir", "/some/dir"),
        (None, os.path.join(os.path.dirname(os.path.abspath(chip_smoke.__file__)), ".jax_cache")),
    ],
    ids=["variable-set", "variable-unset"],
)
def test_compile_cache_is_placed_by_the_variable_or_in_the_checkout(
    monkeypatch, env, want
):
    import jax

    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        assert compile_cache.use_compile_cache() == want
        if env is not None:
            # the variable wins and the code sets no other directory
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_make_mesh_raises_rather_than_build_a_smaller_mesh():
    import jax

    have = len(jax.devices())
    assert make_mesh(have).size == have
    with pytest.raises(ValueError, match=f"asked for {have + 1} devices"):
        make_mesh(have + 1)


def test_engine_refuses_a_mesh_it_cannot_build():
    """Asked for n devices and given fewer is an error at assembly, not a
    silent single-device node."""
    cfg = _test_config()
    cfg.engine.mesh_devices = 64
    with pytest.raises(ValueError, match="asked for 64 devices"):
        LocalNet(1, use_device_verifier=True, config=cfg)


def test_native_rebuild_builds_from_source_and_serves():
    before = os.stat(native._SO).st_ino if os.path.exists(native._SO) else None
    native.rebuild()
    assert native.serving() == "native"
    assert os.stat(native._SO).st_ino != before, "the old library was trusted"
    assert len(native.sha512(b"abc")) == 64
