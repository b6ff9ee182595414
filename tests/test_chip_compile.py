"""Compile the fast path's device programs for the real chip, without one.

The TPU compiler is installed wherever the tests run and compiles for a
*described* v5e (``jax.experimental.topologies``): what the chip's
compiler would refuse — a program that does not fit, a layout it cannot
partition — it refuses here, at no chip time. Nothing executes, so these
tests say nothing about results or speed; ``chip_smoke.py`` does that on
the chip. The shapes are the smoke's own ladder (``chip_smoke.BUCKETS``).

This is the only file that describes the chip. The description happens
inside a module-scoped fixture, never at import: only one process may
load the TPU library, and under pytest-xdist every worker imports every
test file. One compile per test — each is about a minute and the tier-1
audit fails a run with a test over 120 s.
"""

import os

import numpy as np
import pytest

import chip_smoke


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip_compiler(topo):
    """Steer the trace to the chip's formulation and keep the persistent
    cache out of it (a described-device executable is written there but
    can never be read back: the next run would warn and recompile)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    # ops/_fe_common.conv_mode() asks jax.default_backend(), which says
    # cpu here: force the formulation the chip takes
    old_conv = os.environ.get("TXFLOW_FE_CONV")
    os.environ["TXFLOW_FE_CONV"] = "pad"
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", old_cache)
    cc.reset_cache()
    if old_conv is None:
        os.environ.pop("TXFLOW_FE_CONV", None)
    else:
        os.environ["TXFLOW_FE_CONV"] = old_conv


def _step_arg_shapes(b: int, b_slots: int):
    """(shape, dtype) of every argument the verifier hands the packed
    step for a (b votes, b_slots slots) dispatch — recorded from a real
    ``DeviceVoteVerifier.submit`` with the jitted program swapped for a
    recorder, so a change to the dispatch path changes what compiles."""
    from txflow_tpu.verifier import DeviceVoteVerifier

    dv = DeviceVoteVerifier(
        chip_smoke.baseline_validator_set()[1],
        buckets=tuple(sorted({b_slots, b})), staging_ring=0,
    )
    seen = {}

    def recorder(*args):
        seen["args"] = [
            (tuple(np.shape(a)), np.asarray(a).dtype) for a in args
        ]
        return np.zeros(b + 2 * b_slots, np.int32)

    dv._fn = recorder
    dv.submit(
        [b""] * b, [b""] * b, np.zeros(b, np.int64),
        np.zeros(b, np.int64), b_slots,
    )
    return seen["args"]


def _compile_single(topo, b: int, b_slots: int):
    import jax
    from jax.sharding import SingleDeviceSharding

    from txflow_tpu.ops import tally

    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in _step_arg_shapes(b, b_slots)
    ]
    # a fresh jit (not the process-wide lru-cached one): the trace below
    # is steered to the 'pad' convolution and must not be served to — or
    # from — CPU tests that trace the same shapes with 'gather'
    return jax.jit(tally.compact_step_packed()).lower(*args).compile()


def test_packed_step_is_named_for_the_profiler():
    """The step's program is found by name: ``jit_txflow_verify_tally``,
    with the four scopes in its operations' debug info. Lowered only
    (nothing compiles or runs), for the host's own backend, at the
    smallest rung's shapes; first in the file, before ``chip_compiler``
    steers traces to the chip's formulation."""
    import jax

    from txflow_tpu.ops import tally

    b = chip_smoke.BUCKETS[0]
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in _step_arg_shapes(b, b)]
    lowered = tally.compact_step_packed_jit().lower(*args)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_txflow_verify_tally" in text
    for scope in ("decompress", "double_scalar_mul", "encode_compare", "tally"):
        assert f"/{scope}/" in text, scope
    # the other two jitted forms carry names too (no program is jit_f)
    assert tally.compact_step().__name__ == "txflow_verify_tally_unpacked"
    assert tally.verify_and_tally(None).__name__ == "txflow_verify_tally_generic"


def _fits_v5e(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (
        mem.temp_size_in_bytes
        + mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.generated_code_size_in_bytes
    )
    assert total < 16 * 2**30, mem


def test_packed_step_smallest_rung_compiles_for_v5e(chip_compiler):
    b = chip_smoke.BUCKETS[0]
    _fits_v5e(_compile_single(chip_compiler, b, b))


def test_packed_step_4096_rung_compiles_for_v5e(chip_compiler):
    _fits_v5e(_compile_single(chip_compiler, 4096, 4096))


def test_packed_step_4096_votes_small_slots_compiles_for_v5e(chip_compiler):
    _fits_v5e(_compile_single(chip_compiler, 4096, chip_smoke.BUCKETS[0]))


def test_sharded_packed_step_compiles_for_four_v5e(chip_compiler):
    """The ``--chips 4`` phase's program: the packed step shard_map'd over
    a 4-device mesh of the described chips, vote axis split four ways."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from txflow_tpu.parallel.mesh import (
        VOTE_AXIS,
        sharded_compact_step_packed_cached,
    )

    topo = chip_compiler
    assert len(topo.devices) == 4, topo.devices
    mesh = Mesh(np.array(topo.devices), (VOTE_AXIS,))
    split = NamedSharding(mesh, P(VOTE_AXIS))
    rep = NamedSharding(mesh, P())
    b = b_slots = 4096
    shapes = _step_arg_shapes(b, b_slots)
    # the vote rows shard over the vote axis; tables, powers and the slot
    # state (prior stake + quorum) replicate (DeviceVoteVerifier.submit)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=split if i == 0 else rep)
        for i, (shape, dtype) in enumerate(shapes)
    ]
    # lru-cached per mesh: no CPU test shares this mesh of described chips
    compiled = sharded_compact_step_packed_cached(mesh).lower(*args).compile()
    _fits_v5e(compiled)
    text = compiled.as_text()
    assert "all-reduce" in text, "the stake tally must psum across the mesh"
    out = compiled.output_shardings
    assert out.spec == P(VOTE_AXIS), out
