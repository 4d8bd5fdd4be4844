"""Unified retry/backoff engine for storage and KV transients.

Extracted from the GCS plugin's collective-progress retry (previously
``storage/gcs.py _CollectiveProgressRetry``) and generalized so every
backend shares one policy:

- **SharedProgress** — the shared-deadline window: all concurrent ops
  on a plugin share one clock that is refreshed whenever *any* op
  completes, so an op only gives up when the whole pipeline has made no
  progress for the window.  Transient per-connection stalls can't fail
  a 30-minute snapshot, while a genuinely dead backend still fails
  within one window.
- **retry_call** — the retry loop: run the op, classify failures
  (transient / missing / fatal), back off exponentially with
  deterministic jitter on transients, respect the shared window and
  per-op attempt cap, and feed the per-backend circuit breaker.

Classification verdicts (returned by a backend's ``classify(e)``):

- ``"transient"``  — retry with backoff (throttle, 5xx, connection
  reset, EINTR/EAGAIN).
- ``"missing"``    — raise ``FileNotFoundError`` chaining the original
  (the cross-plugin cold-start contract).
- ``"fatal"``      — re-raise the original; counts as a breaker failure.
- ``"raise"``      — re-raise the original; NOT a breaker failure
  (deterministic non-backend outcomes, e.g. a 416 on a zero-byte read).
- ``"success_none"`` — swallow and return None (e.g. idempotent
  delete of a missing object).

Policy knobs: ``TORCHSNAPSHOT_TPU_RETRY_MAX_ATTEMPTS``,
``RETRY_BACKOFF_CAP_S``.  Hand-rolled
sleep-backoff loops around storage/KV ops elsewhere in the package are
rejected by the snaplint ``retry-discipline`` pass — this module is the
one sanctioned home for them.
"""

from __future__ import annotations

import asyncio
import errno as _errno
import logging
import random
import time
import zlib
from typing import Any, Callable, Optional

from .. import knobs, obs

logger = logging.getLogger(__name__)

TRANSIENT = "transient"
MISSING = "missing"
FATAL = "fatal"
RAISE = "raise"
SUCCESS_NONE = "success_none"

_VERDICTS = frozenset((TRANSIENT, MISSING, FATAL, RAISE, SUCCESS_NONE))


class SharedProgress:
    """Shared-deadline retry window (the reference _RetryStrategy,
    gcs.py:221-277, by way of the GCS plugin's _CollectiveProgressRetry):
    any completion anywhere refreshes the clock."""

    def __init__(
        self,
        window_s: float = 120.0,
        max_attempts: Optional[int] = None,
        label: str = "",
    ) -> None:
        # an op gives up only when the WHOLE pipeline has made no
        # progress this long (the GCS plugin's historical constant)
        self.window_s = window_s
        self.max_attempts = (
            knobs.get_retry_max_attempts() if max_attempts is None
            else max_attempts
        )
        self.last_progress = time.monotonic()
        # private, deterministically seeded stream: backoff jitter
        # (possibly on the async-commit background thread) must never
        # perturb the global random state the take-path RNG invariant
        # protects, and the same label replays the same jitter sequence
        self._rng = random.Random(0x5EED ^ zlib.crc32(label.encode()))

    def record_progress(self) -> None:
        self.last_progress = time.monotonic()

    def should_retry(
        self, attempt: int, started: Optional[float] = None
    ) -> bool:
        """``started``: when the CURRENT op began — the window must
        never count idle time from before the op existed.  A
        SharedProgress can sit idle arbitrarily long between operations
        (a process-global one like the codec's encodes; a plugin that
        last saw traffic minutes ago), and without the floor the first
        transient after such a gap would read as "no progress for the
        whole window" and surface un-retried."""
        if attempt >= self.max_attempts:
            return False
        anchor = self.last_progress
        if started is not None and started > anchor:
            anchor = started
        return (time.monotonic() - anchor) < self.window_s

    def backoff_delay(self, attempt: int) -> float:
        cap = knobs.get_retry_backoff_cap_s()
        return min(2**attempt, cap) * (0.5 + self._rng.random())

    async def backoff(self, attempt: int) -> None:
        delay = self.backoff_delay(attempt)
        obs.histogram(obs.RESILIENCE_BACKOFF_DELAY_S).observe(delay)
        await asyncio.sleep(delay)


def lazy_shared_progress(obj: Any, label: str) -> SharedProgress:
    """Get-or-create ``obj._progress`` (one SharedProgress per plugin
    instance).  Via ``__dict__`` on purpose: contract-test doubles build
    plugins with ``__new__`` + attribute assignment and must work
    without running ``__init__``."""
    p = obj.__dict__.get("_progress")
    if p is None:
        p = obj.__dict__["_progress"] = SharedProgress(label=label)
    return p


async def retry_call(
    fn: Callable[[], Any],
    *,
    op_name: str,
    backend: str,
    classify: Callable[[BaseException], str],
    progress: SharedProgress,
    executor: Any = None,
    breaker: Any = None,
) -> Any:
    """Run ``fn`` under the shared retry policy.  ``fn`` is a plain
    callable executed on ``executor`` when one is given (the storage
    plugins' thread-pool pattern) or awaited directly when it returns a
    coroutine.  ``breaker``: an optional CircuitBreaker consulted before
    the first attempt (open -> fail fast) and fed the op's final
    outcome."""
    if breaker is not None:
        breaker.check(op_name)
    try:
        return await _retry_loop(
            fn, op_name, backend, classify, progress, executor, breaker
        )
    except BaseException:
        # whatever escapes (classified fatals already recorded; but also
        # cancellation/KeyboardInterrupt, which the loop never
        # classifies) must not leave a half-open probe slot claimed —
        # releasing after record_success/record_failure is a no-op
        if breaker is not None:
            breaker.release_probe()
        raise


async def _retry_loop(
    fn, op_name, backend, classify, progress, executor, breaker
) -> Any:
    attempt = 0
    # floor for the progress window: idle time BEFORE this op began is
    # not this op's stall (see SharedProgress.should_retry)
    started = time.monotonic()
    # the most recent backoff span: the retry sequence's FINAL verdict
    # (success / fatal / exhausted) is stamped onto it when the loop
    # resolves, so a trace shows how each backoff chain ended without
    # correlating spans by hand (the Span object stays referenced by
    # the tracer, so post-close attr stamps reach the export)
    last_backoff_span = None

    def _stamp_final(verdict: str) -> None:
        if last_backoff_span is not None:
            last_backoff_span.attrs["final_verdict"] = verdict

    while True:
        try:
            if executor is not None:
                # traced, the attempt's spans (fastio/*, storage/mmap_read)
                # nest under the storage span that asked for it
                result = await obs.run_in_executor(
                    executor, fn, name="storage/attempt"
                )
            else:
                result = fn()
                if asyncio.iscoroutine(result):
                    result = await result
            progress.record_progress()
            if breaker is not None:
                breaker.record_success()
            _stamp_final("success")
            return result
        except FileNotFoundError:
            # missing is an answer, not a backend failure (but a
            # half-open probe slot must not stay claimed)
            if breaker is not None:
                breaker.release_probe()
            raise
        # Exception, NOT BaseException: cancellation, KeyboardInterrupt
        # and SystemExit must propagate immediately — classifying them
        # would retry through a cancellation (wedging wait_for past its
        # timeout) or count healthy-backend teardown as breaker failures
        except Exception as e:  # noqa: BLE001 — classified below
            verdict = classify(e)
            if verdict not in _VERDICTS:
                raise AssertionError(
                    f"classifier for {backend} returned {verdict!r}"
                ) from e
            if verdict == MISSING:
                if breaker is not None:
                    breaker.release_probe()
                raise FileNotFoundError(f"{op_name}: {e}") from e
            if verdict == SUCCESS_NONE:
                progress.record_progress()
                if breaker is not None:
                    breaker.record_success()
                return None
            if verdict == RAISE:
                if breaker is not None:
                    breaker.release_probe()
                raise
            if verdict == FATAL:
                if breaker is not None:
                    breaker.record_failure()
                _stamp_final("fatal")
                raise
            attempt += 1
            obs.counter(obs.RESILIENCE_RETRIES).inc()
            obs.counter(f"resilience.{backend}.retries").inc()
            if not progress.should_retry(attempt, started=started):
                if breaker is not None:
                    breaker.record_failure()
                _stamp_final("exhausted")
                raise
            logger.warning(
                "%s %s failed (attempt %d, retrying): %r",
                backend, op_name, attempt, e,
            )
            # attempt + triggering verdict ride the span so a trace can
            # reconstruct each backoff chain without log correlation
            with obs.span(
                "resilience/backoff",
                backend=backend, op=op_name, attempt=attempt,
                verdict=verdict,
            ) as sp:
                if sp is not None:
                    last_backoff_span = sp
                await progress.backoff(attempt)


# ------------------------------------------------------- classifiers


_FS_TRANSIENT_ERRNOS = frozenset((_errno.EINTR, _errno.EAGAIN))


def classify_fs(e: BaseException) -> str:
    """Local filesystem: EINTR/EAGAIN are the retriable transients; a
    missing file already surfaces as FileNotFoundError (passed through
    by the engine) and anything else (ENOSPC, EIO, ...) is fatal."""
    if isinstance(e, OSError) and e.errno in _FS_TRANSIENT_ERRNOS:
        return TRANSIENT
    return FATAL


def _client_error_code(e: BaseException) -> str:
    return str(getattr(e, "response", {}).get("Error", {}).get("Code", ""))


def _http_status(e: BaseException) -> Optional[int]:
    status = (
        getattr(e, "response", {})
        .get("ResponseMetadata", {})
        .get("HTTPStatusCode")
    )
    return status if isinstance(status, int) else None


# NoSuchUpload: the multipart-upload twin of NoSuchKey — an abort/part
# op against an upload id that no longer exists (already aborted or
# completed); maps to MISSING so abort-on-cleanup stays idempotent
_S3_MISSING_CODES = frozenset(("NoSuchKey", "NoSuchUpload", "404"))
_S3_TRANSIENT_CODES = frozenset(
    (
        "SlowDown",
        "Throttling",
        "ThrottlingException",
        "RequestTimeout",
        "RequestLimitExceeded",
        "ServiceUnavailable",
        "InternalError",
        "500",
        "502",
        "503",
        "504",
    )
)


def classify_s3(e: BaseException) -> str:
    """S3: explicit transient vs. missing vs. fatal — a transient 500
    must retry (and, exhausted, surface as ITSELF), never masquerade as
    some other failure with the original context lost."""
    code = _client_error_code(e)
    name = type(e).__name__
    if code in _S3_MISSING_CODES or name == "NoSuchKey":
        return MISSING
    if code in _S3_TRANSIENT_CODES or name == "SlowDown":
        return TRANSIENT
    if isinstance(e, (ConnectionError, TimeoutError)):
        return TRANSIENT
    # botocore's connection-layer errors don't subclass the builtins
    # (EndpointConnectionError, ConnectTimeoutError, ReadTimeoutError,
    # IncompleteReadError ...)
    if "ConnectionError" in name or "Timeout" in name:
        return TRANSIENT
    status = _http_status(e)
    if status is not None and status >= 500:
        return TRANSIENT
    return FATAL


def classify_generic(e: BaseException) -> str:
    """Backends with no richer signal (memory://, third-party plugins):
    connection/timeout shapes and EINTR/EAGAIN retry, the rest is
    fatal."""
    if isinstance(e, (ConnectionError, TimeoutError)):
        return TRANSIENT
    return classify_fs(e)
