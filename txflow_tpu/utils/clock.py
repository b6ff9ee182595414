"""Wall-clock seam for consensus-critical modules.

txlint's ``nondeterminism`` rule forbids raw ``time.time()`` /
``time.time_ns()`` (and unseeded rng) inside certificate- and
consensus-critical modules (types/vote_set, engine/txflow, consensus/*):
a timestamp read mid-decision is a per-node value that lands in signed
artifacts (proposal timestamps) and replay logs, and scattering direct
clock reads makes "pin the clock" impossible in tests and replays.

This module is the one sanctioned source: consensus code imports
``now_ns``/``now`` from here, tests monkeypatch here, and the lint pass
whitelists calls routed through these names. Keep it free of any other
dependency — it is imported by the lowest layers.

The monotonic seams below exist for the tracing subsystem (trace/):
every timestamp that can land in a trace span must come from here, so a
replay can pin ONE module and get deterministic spans, and so txlint's
``trace-clock`` pass can forbid raw ``time.monotonic``/``perf_counter``
in the traced hot-path modules without whitelisting call sites one by
one.
"""

from __future__ import annotations

import time


def now_ns() -> int:
    """Wall-clock nanoseconds (proposal timestamps, commit times)."""
    return time.time_ns()


def now() -> float:
    """Wall-clock seconds."""
    return time.time()


def monotonic() -> float:
    """Monotonic seconds (deadlines, linger windows, trace spans)."""
    return time.monotonic()


def monotonic_ns() -> int:
    """Monotonic nanoseconds."""
    return time.monotonic_ns()


def perf_counter() -> float:
    """High-resolution monotonic seconds (stage timing, trace spans)."""
    return time.perf_counter()


def perf_counter_ns() -> int:
    """High-resolution monotonic nanoseconds."""
    return time.perf_counter_ns()


def thread_time() -> float:
    """CPU seconds of the calling thread: what a stretch of code cost the
    interpreter, whoever else held its lock meanwhile. A cost counter's
    clock, never a span's timestamp."""
    return time.thread_time()
