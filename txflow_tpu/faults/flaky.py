"""FlakyVerifier: scripted device-verifier failures.

A transparent proxy around any verifier that raises
``InjectedDeviceError`` on a deterministic schedule — the first N calls,
an explicit call-index set, or whenever ``failing`` is toggled on (for
"device dies mid-run, later recovers" scenarios). Wrapped in
``verifier.ResilientVoteVerifier`` it exercises the full degradation
policy: retry/backoff, CPU fallback, and device re-promotion.
"""

from __future__ import annotations


class InjectedDeviceError(RuntimeError):
    """A deliberately injected device-verifier failure."""


class FlakyVerifier:
    def __init__(
        self,
        inner,
        fail_first: int = 0,
        fail_calls=(),
        error_factory=None,
        fail_at: str = "result",
    ):
        # fail_at governs WHERE a scheduled failure surfaces on the async
        # submit path: "result" (default) models a readback/transport
        # failure — submit succeeds, ticket.result() raises — which is
        # where a real device loss usually lands once dispatch is async;
        # "submit" models a dispatch failure (enqueue itself errors).
        # The blocking verify_and_tally path always raises inline.
        if fail_at not in ("result", "submit"):
            raise ValueError("fail_at must be 'result' or 'submit'")
        self.inner = inner
        self.val_set = inner.val_set
        mb = getattr(inner, "max_batch", None)
        if mb is not None:
            self.max_batch = mb
        self.fail_at = fail_at
        self.fail_first = fail_first
        self.fail_calls = set(fail_calls)
        self.failing = False  # toggle: fail every call while True
        self.calls = 0
        self.failures = 0
        self._make_error = error_factory or (
            lambda i: InjectedDeviceError(f"injected device failure (call {i})")
        )

    def warmup(self, n: int = 1, full: bool = False) -> None:
        self.inner.warmup(n, full=full)

    def _due(self) -> int | None:
        """Advance the call counter; return the call index if this call
        is scheduled to fail, else None."""
        i = self.calls
        self.calls += 1
        if self.failing or i < self.fail_first or i in self.fail_calls:
            self.failures += 1
            return i
        return None

    def verify_and_tally(self, *args, **kwargs):
        i = self._due()
        if i is not None:
            raise self._make_error(i)
        return self.inner.verify_and_tally(*args, **kwargs)

    def submit(self, *args, **kwargs):
        from ..verifier import ReadyTicket

        i = self._due()
        if i is not None:
            if self.fail_at == "submit":
                raise self._make_error(i)
            return _FailAtResultTicket(self._make_error(i))
        sub = getattr(self.inner, "submit", None)
        if sub is not None:
            return sub(*args, **kwargs)
        return ReadyTicket(self.inner.verify_and_tally(*args, **kwargs))


class _FailAtResultTicket:
    """Ticket whose dispatch 'succeeded' but whose readback fails —
    exercises collect-time degradation (ResilientVoteVerifier's
    _ResilientTicket policy re-run)."""

    __slots__ = ("_err",)

    def __init__(self, err: Exception):
        self._err = err

    def result(self):
        raise self._err
