"""Shared retry/deadline policy for every bounded-time loop in the engine.

Reference parity: the reference scatters retry logic across the FTS probe
FSM (ftsprobe.c restart/backoff), libpq connect retries in cdbgang.c, and
dispatcher wait timeouts (poll() with gp_segment_connect_timeout).  Ours
centralizes the three primitives they all share:

  * ``Deadline``    — a monotonic budget that can be split across steps
                      (connect, handshake, per-ack reads) without drifting,
  * ``backoff_delays`` — exponential backoff with full jitter (the
                      AWS-style decorrelated sleep that avoids thundering
                      herds when a whole gang reconnects at once),
  * ``RetryPolicy`` — retry-a-callable with retryable-error classification,
                      bounded by attempts and/or a deadline.

This module is intentionally stdlib-only: the control channel uses it
before any device runtime exists.
"""

from __future__ import annotations

import random
import socket
import sys
import time


def _check_interrupts() -> None:
    """Interrupt poll that keeps this module stdlib-only: when the engine
    is loaded, retry sleeps are statement cancellation points (PR-4
    discipline); where the registry module is not loaded this is a
    no-op."""
    mod = sys.modules.get("greengage_tpu.runtime.interrupt")
    if mod is not None:
        mod.check_interrupts()

# Errors that indicate a transient transport condition: the peer is not
# (yet) reachable or the exchange timed out — retrying can succeed.
# Anything else (protocol garbage, programming errors) must propagate.
TRANSIENT_ERRORS = (
    ConnectionError,          # refused / reset / aborted / broken pipe
    socket.timeout,           # alias of TimeoutError on 3.10+, kept explicit
    TimeoutError,
    InterruptedError,
    socket.gaierror,          # transient resolver failure on reconnect
)


class Deadline:
    """A monotonic time budget. ``Deadline(None)`` never expires."""

    __slots__ = ("seconds", "_end")

    def __init__(self, seconds: float | None):
        self.seconds = seconds
        self._end = None if seconds is None else time.monotonic() + seconds

    @classmethod
    def after(cls, seconds: float | None) -> "Deadline":
        return cls(seconds)

    @property
    def expired(self) -> bool:
        return self._end is not None and time.monotonic() >= self._end

    def remaining(self, minimum: float = 0.0) -> float | None:
        """Seconds left (>= minimum), or None for an unbounded deadline."""
        if self._end is None:
            return None
        return max(minimum, self._end - time.monotonic())

    def clamp(self, seconds: float) -> float:
        """Bound a step's own timeout by what's left of the budget."""
        rem = self.remaining()
        return seconds if rem is None else min(seconds, rem)

    def require(self, what: str) -> None:
        """Raise TimeoutError if the budget is spent (named for the log)."""
        if self.expired:
            raise TimeoutError(
                f"{what} exceeded the {self.seconds:.1f}s deadline")


def backoff_delays(base: float = 0.1, factor: float = 2.0, cap: float = 30.0,
                   jitter: float = 0.5, deadline: Deadline | None = None):
    """Yield exponentially growing sleep lengths with full jitter.

    Each delay is drawn uniformly from
    ``[d * (1 - jitter), d * (1 + jitter)]`` where ``d`` doubles (by
    ``factor``) from ``base`` up to ``cap``.  With a ``deadline``, delays
    are clamped to the remaining budget and the generator stops once the
    budget is spent (so callers can ``for delay in ...: sleep(delay)``).
    """
    d = base
    while True:
        if deadline is not None and deadline.expired:
            return
        lo, hi = d * (1.0 - jitter), d * (1.0 + jitter)
        delay = random.uniform(max(0.0, lo), hi)
        if deadline is not None:
            delay = deadline.clamp(delay)
        yield delay
        d = min(d * factor, cap)


class RetryPolicy:
    """Retry a callable on transient errors, bounded by attempts and/or a
    deadline.  The last error propagates when the budget is spent."""

    def __init__(self, deadline_s: float | None = None,
                 attempts: int | None = None, base_s: float = 0.1,
                 factor: float = 2.0, cap_s: float = 5.0,
                 jitter: float = 0.5, retryable: tuple = TRANSIENT_ERRORS):
        if deadline_s is None and attempts is None:
            raise ValueError("RetryPolicy needs a deadline and/or attempts")
        self.deadline_s = deadline_s
        self.attempts = attempts
        self.base_s = base_s
        self.factor = factor
        self.cap_s = cap_s
        self.jitter = jitter
        self.retryable = retryable

    def call(self, fn, on_retry=None):
        deadline = Deadline(self.deadline_s)
        delays = backoff_delays(self.base_s, self.factor, self.cap_s,
                                self.jitter, deadline)
        attempt = 0
        while True:
            attempt += 1
            try:
                return fn()
            except self.retryable as e:
                out_of_attempts = (self.attempts is not None
                                   and attempt >= self.attempts)
                delay = None if out_of_attempts else next(delays, None)
                if delay is None:      # budget spent (attempts or deadline)
                    raise
                if on_retry is not None:
                    try:
                        on_retry(attempt, e, delay)
                    except Exception:
                        pass
                # backoff in short slices so a cancel LANDING mid-sleep
                # fires within ~0.25s, not after the full delay (cap_s=5)
                until = time.monotonic() + delay
                while True:
                    _check_interrupts()
                    rem = until - time.monotonic()
                    if rem <= 0:
                        break
                    time.sleep(min(rem, 0.25))
