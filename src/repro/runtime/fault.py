"""Fault tolerance & straggler mitigation for the training runtime.

On a real cluster the failure domains are: device loss (XLA raises), host
loss (process death — covered by checkpoint/restart + deterministic data
replay), and slow nodes.  This module provides the single-process pieces:

* ``resilient_step`` — retries a step on transient errors with exponential
  backoff; non-transient (deterministic) errors re-raise immediately.
  After ``max_retries`` it raises ``StepFailed`` so the launcher can
  checkpoint-restart (or shrink the mesh — see ``elastic.py``).  What
  counts as transient is deliberately narrow (:func:`is_transient`):
  connection/timeout OS errors, plus XLA runtime errors whose message
  carries an explicitly-transient RPC status (UNAVAILABLE, DEADLINE
  EXCEEDED, ...).  A bare ``RuntimeError`` is *not* transient — retrying
  a deterministic failure (shape error, NaN guard, assertion) just burns
  ``max_retries`` walltime before failing anyway, and in the serving
  heal path would triple-program a band for nothing.
* ``StragglerMonitor`` — tracks per-step wall times, flags ``> mean +
  k*std`` outliers, and calls an eviction hook.  On multi-pod deployments
  the hook would demote the slow host and trigger an elastic restart; here
  it records the event (tested with injected delays).
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, List, Optional, Tuple

#: exception types that are transient *by construction* — lost
#: connections and timeouts get retried, everything else re-raises.
#: (``OSError``/``RuntimeError`` wholesale would swallow deterministic
#: failures: FileNotFoundError is an OSError, XLA shape errors are
#: RuntimeErrors.)
TRANSIENT_ERRORS = (
    ConnectionError,
    TimeoutError,
    InterruptedError,
)

#: RPC status fragments marking a jaxlib ``XlaRuntimeError`` (a
#: RuntimeError subclass with no stable taxonomy of its own) as
#: transient: gRPC/absl status codes of retryable distributed-runtime
#: failures, plus device-side transfer hiccups.
TRANSIENT_XLA_MESSAGES = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "DEADLINE EXCEEDED",
    "ABORTED",
    "RESOURCE_EXHAUSTED",
    "RESOURCE EXHAUSTED",
    "failed to transfer",
    "connection reset",
)


class StepFailed(RuntimeError):
    pass


def is_transient(e: BaseException) -> bool:
    """Is ``e`` worth retrying?  Explicit transient types, or an XLA
    runtime error whose status string is on the transient allowlist."""
    if isinstance(e, TRANSIENT_ERRORS):
        return True
    if type(e).__name__ == "XlaRuntimeError":
        msg = str(e).upper()
        return any(frag.upper() in msg for frag in TRANSIENT_XLA_MESSAGES)
    return False


def resilient_step(
    fn: Callable,
    *args,
    max_retries: int = 3,
    backoff_s: float = 0.05,
    transient: Optional[Tuple] = None,
    on_retry: Optional[Callable[[int, BaseException], None]] = None,
    **kwargs,
):
    attempt = 0
    while True:
        try:
            return fn(*args, **kwargs)
        except Exception as e:
            retryable = (is_transient(e) if transient is None
                         else isinstance(e, transient))
            if not retryable:
                raise
            attempt += 1
            if on_retry is not None:
                on_retry(attempt, e)
            if attempt > max_retries:
                raise StepFailed(
                    f"step failed after {max_retries} retries: {e!r}"
                ) from e
            time.sleep(backoff_s * (2 ** (attempt - 1)))


class StragglerMonitor:
    def __init__(self, *, k_sigma: float = 3.0, window: int = 50,
                 min_samples: int = 10,
                 on_straggler: Optional[Callable[[int, float], None]] = None):
        self.k = k_sigma
        self.window = window
        self.min_samples = min_samples
        self.on_straggler = on_straggler
        self.times: List[float] = []
        self.flagged: List[Tuple[int, float]] = []
        self._step = 0

    def record(self, dt: float) -> bool:
        """Record one step duration; returns True if flagged."""
        self._step += 1
        hist = self.times[-self.window:]
        flagged = False
        if len(hist) >= self.min_samples:
            mu = statistics.fmean(hist)
            sd = statistics.pstdev(hist) or 1e-9
            if dt > mu + self.k * sd:
                flagged = True
                self.flagged.append((self._step, dt))
                if self.on_straggler is not None:
                    self.on_straggler(self._step, dt)
        self.times.append(dt)
        return flagged

    def timed(self, fn: Callable, *args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.record(time.perf_counter() - t0)
        return out

