"""Per-backend circuit breaker: fail fast when a backend is down.

Classic three-state breaker fed by the retry engine (retry.py): only
COMPLETED failures count (an op whose retries were exhausted, or a
fatal classification) — an op that recovered on retry is a success.

- **closed** — normal operation; consecutive failures are counted.
- **open** — ``threshold`` consecutive failures tripped it: ``check()``
  raises ``CircuitOpenError`` immediately (writes fail fast instead of
  burning a full retry window each; tiered reads route straight to the
  replica/durable fallback) until the cooldown elapses.
- **half-open** — after the cooldown one probe op is allowed through;
  its success closes the breaker, its failure re-opens (fresh cooldown).

Knob: ``TORCHSNAPSHOT_TPU_BREAKER_THRESHOLD`` (consecutive failures);
the cooldown is the constructor's ``cooldown_s``.  State is exported as
the gauge ``resilience.breaker_state.<name>`` (0 closed, 1 half-open,
2 open) and trips count ``resilience.breaker_trips``.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, Optional

from .. import knobs, obs

logger = logging.getLogger(__name__)

CLOSED = "closed"
HALF_OPEN = "half_open"
OPEN = "open"

_STATE_GAUGE_VALUES = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}


class CircuitOpenError(OSError):
    """The backend's breaker is open: failing fast instead of issuing
    an op that would burn a full retry window.  An OSError so existing
    per-backend error handling (fallbacks, fatal classification) treats
    it as the I/O failure it stands in for."""

    def __init__(self, name: str, op_name: str, retry_in_s: float) -> None:
        super().__init__(
            f"circuit breaker for {name!r} is open ({op_name}): backend "
            f"failing consecutively; next probe allowed in "
            f"{max(0.0, retry_in_s):.1f}s"
        )
        self.breaker_name = name


class CircuitBreaker:
    def __init__(
        self,
        name: str,
        threshold: Optional[int] = None,
        cooldown_s: float = 30.0,
    ) -> None:
        self.name = name
        self._threshold = threshold
        # open this long before one half-open probe is admitted
        self.cooldown_s = cooldown_s
        self._lock = threading.Lock()
        self._consecutive_failures = 0
        self._state = CLOSED
        self._opened_at = 0.0
        self._probe_in_flight = False
        self._gauge = obs.gauge(f"resilience.breaker_state.{name}")
        self._gauge.set(0)

    # knob-resolved per use so test overrides take effect mid-life
    @property
    def threshold(self) -> int:
        return (
            knobs.get_breaker_threshold() if self._threshold is None
            else self._threshold
        )

    @property
    def state(self) -> str:
        with self._lock:
            return self._effective_state()

    def _effective_state(self) -> str:
        # lock held.  An open breaker whose cooldown elapsed presents as
        # half-open (the next allow() admits one probe).
        if self._state == OPEN and (
            time.monotonic() - self._opened_at >= self.cooldown_s
        ):
            self._state = HALF_OPEN
            self._probe_in_flight = False
            self._gauge.set(_STATE_GAUGE_VALUES[HALF_OPEN])
        return self._state

    def allow(self) -> bool:
        """True when an op may be issued now.  In half-open, exactly one
        probe is admitted until its outcome is recorded."""
        with self._lock:
            state = self._effective_state()
            if state == CLOSED:
                return True
            if state == OPEN:
                return False
            if self._probe_in_flight:
                return False
            self._probe_in_flight = True
            return True

    def check(self, op_name: str = "") -> None:
        """allow() or raise CircuitOpenError (the retry engine's entry
        gate)."""
        if not self.allow():
            with self._lock:
                retry_in = self.cooldown_s - (
                    time.monotonic() - self._opened_at
                )
            raise CircuitOpenError(self.name, op_name, retry_in)

    def record_success(self) -> None:
        with self._lock:
            self._consecutive_failures = 0
            self._probe_in_flight = False
            if self._state != CLOSED:
                logger.info(
                    "circuit breaker %r closed (probe succeeded)", self.name
                )
            self._state = CLOSED
            self._gauge.set(_STATE_GAUGE_VALUES[CLOSED])

    def release_probe(self) -> None:
        """The op's outcome said nothing about backend health (e.g. a
        genuine not-found): release the half-open probe slot without
        recording success or failure, so the breaker can't wedge
        half-open waiting for an outcome that never arrives."""
        with self._lock:
            self._probe_in_flight = False

    def record_failure(self) -> None:
        with self._lock:
            self._probe_in_flight = False
            self._consecutive_failures += 1
            failures = self._consecutive_failures
            tripped = (
                self._state == HALF_OPEN
                or (
                    self._state == CLOSED
                    and failures >= self.threshold
                )
            )
            if tripped:
                self._state = OPEN
                self._opened_at = time.monotonic()
                self._gauge.set(_STATE_GAUGE_VALUES[OPEN])
        if tripped:
            obs.counter(obs.RESILIENCE_BREAKER_TRIPS).inc()
            logger.warning(
                "circuit breaker %r tripped open after %d consecutive "
                "failure(s); failing fast for %.1fs",
                self.name, failures, self.cooldown_s,
            )

    def reset(self) -> None:
        with self._lock:
            self._consecutive_failures = 0
            self._state = CLOSED
            self._probe_in_flight = False
            self._gauge.set(0)


_REGISTRY: Dict[str, CircuitBreaker] = {}
_REGISTRY_LOCK = threading.Lock()


def get_breaker(name: str) -> CircuitBreaker:
    """Process-global breaker per backend name, get-or-create."""
    with _REGISTRY_LOCK:
        b = _REGISTRY.get(name)
        if b is None:
            b = _REGISTRY[name] = CircuitBreaker(name)
        return b


def reset_breakers() -> None:
    """Close every registered breaker (tests)."""
    with _REGISTRY_LOCK:
        breakers = list(_REGISTRY.values())
    for b in breakers:
        b.reset()
