"""Test configuration: the tests run on the CPU, on an 8-device virtual mesh.

The chip is never used here: ``JAX_PLATFORMS=cpu`` is forced below (env
var before the import, config after it, so a process that imported jax
earlier is pinned too), and XLA's host-platform device-count override —
in place before the CPU client is first created — gives the sharding
tests their devices. What runs on the chip is ``python chip_smoke.py``
through the chip tool; what the chip's compiler says of the real shapes
is ``tests/test_chip_compile.py``, which describes the chip inside its
own fixture and nowhere else.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Runtime lock-order auditing is ON for the whole tier-1 suite (must be
# set before any txflow_tpu module constructs a lock). Opt out of the
# audit by exporting TXFLOW_LOCK_AUDIT=0 explicitly.
os.environ.setdefault("TXFLOW_LOCK_AUDIT", "1")
# Lockset race auditing (analysis/racegraph.py) rides on the lock audit:
# every declared shared field's accesses are checked Eraser-style across
# the whole suite, and the sessionfinish gate below fails the run on any
# race report. Opt out with TXFLOW_RACE_AUDIT=0.
os.environ.setdefault("TXFLOW_RACE_AUDIT", "1")

import jax

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, (
    "test contract requires an 8-device virtual CPU mesh, got "
    f"{jax.devices()}"
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compile cache (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache): many test files independently jit the same
# bucket-shaped programs, and each fresh function object misses the
# in-memory jit cache even when the HLO is identical — the disk cache
# turns those (and every compile of a rerun suite) into loads. Exported,
# so the child processes some tests start (test_fe13's radix-13 runs)
# share the same directory.
from txflow_tpu.utils.compile_cache import use_compile_cache  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", use_compile_cache())


# Headroom for deadlines in the network and recovery drills: the driver
# runs the suite with six workers on eight cores, where a wait that holds
# alone (a re-dial, a subprocess start, a commit through lossy links) can
# overrun. Tests multiply their waits by this ONE factor; a wait returns
# as soon as its condition holds, so a passing test costs nothing more.
WAIT_FACTOR = 3


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running soak scenarios (tier-1 runs -m 'not slow')"
    )
    if os.environ.get("TXFLOW_LOCK_AUDIT") == "1":
        from txflow_tpu.analysis.lockgraph import install_probes

        install_probes()


# -- tier-1 time-budget audit -------------------------------------------
#
# Tier-1 runs ``-m 'not slow'`` under a hard wall-clock timeout, so a
# single unmarked test that balloons past the per-test budget silently
# eats the whole suite's headroom. The audit records call-phase durations
# and fails the RUN (without un-passing the tests) when an unmarked test
# exceeds TXFLOW_TIER1_TEST_BUDGET seconds — the fix is either to speed
# the test up or to mark it ``slow`` and move it out of tier-1.

_TIER1_BUDGET = float(os.environ.get("TXFLOW_TIER1_TEST_BUDGET", "120"))
_durations: dict = {}
_slow_marked: set = set()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow") is not None:
            _slow_marked.add(item.nodeid)


def pytest_runtest_logreport(report):
    if report.when == "call":
        _durations[report.nodeid] = report.duration


def _lock_audit_gate(session):
    """Fail the RUN (without un-passing tests) if the runtime lock-order
    auditor observed a cycle in the acquisition graph or a lock held
    across a declared blocking call anywhere in the suite."""
    if os.environ.get("TXFLOW_LOCK_AUDIT") != "1":
        return
    from txflow_tpu.analysis.lockgraph import default_auditor

    report = default_auditor().report()
    cycles = report["cycles"]
    blocking = report["blocking_violations"]
    if not cycles and not blocking:
        return
    lines = ["runtime lock audit: violations observed during the suite:"]
    for cyc in cycles:
        lines.append(f"  lock-order cycle: {' -> '.join(cyc)}")
    for bv in blocking:
        lines.append(
            f"  blocking call {bv['desc']!r} while holding "
            f"{bv['held']} (thread {bv['thread']})"
        )
        if bv.get("stack"):
            lines.append(f"    at: {bv['stack']}")
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.section("runtime lock audit", sep="=")
        for line in lines:
            tr.write_line(line)
    else:
        print("\n".join(lines))
    if session.exitstatus == 0:
        session.exitstatus = 1


def _race_audit_gate(session):
    """Fail the RUN on any lockset race report, and dump the full field
    summary to .race_audit.json (repo root) for `tools/lint.py
    --race-report` — mirrors the lock-audit gate above."""
    if os.environ.get("TXFLOW_RACE_AUDIT") != "1":
        return
    if os.environ.get("TXFLOW_LOCK_AUDIT") != "1":
        return  # locksets come from the lock audit; nothing was recorded
    import json

    from txflow_tpu.analysis.racegraph import default_race_auditor

    report = default_race_auditor().report()
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".race_audit.json",
    )
    try:
        with open(out, "w") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
    except OSError:
        pass
    races = report["races"]
    if not races:
        return
    lines = ["runtime race audit: lockset violations observed during the suite:"]
    for r in races:
        lines.append(
            f"  {r['field']}: unlocked {r['access']} at {r['site']} "
            f"(thread {r['thread']}) races {r['other_site']} "
            f"(thread {r['other_thread']})"
        )
        if r.get("stack"):
            lines.append(f"    at: {r['stack']}")
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.section("runtime race audit", sep="=")
        for line in lines:
            tr.write_line(line)
    else:
        print("\n".join(lines))
    if session.exitstatus == 0:
        session.exitstatus = 1


def pytest_sessionfinish(session, exitstatus):
    _lock_audit_gate(session)
    _race_audit_gate(session)
    offenders = sorted(
        (
            (dur, nodeid)
            for nodeid, dur in _durations.items()
            if dur > _TIER1_BUDGET and nodeid not in _slow_marked
        ),
        reverse=True,
    )
    if not offenders:
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    lines = [
        "tier-1 marker audit: unmarked tests exceeded the "
        f"{_TIER1_BUDGET:g}s budget (mark them `slow` or speed them up):"
    ] + [f"  {dur:8.1f}s  {nodeid}" for dur, nodeid in offenders]
    if tr is not None:
        tr.section("tier-1 time budget", sep="=")
        for line in lines:
            tr.write_line(line)
    else:
        print("\n".join(lines))
    if session.exitstatus == 0:
        session.exitstatus = 1
