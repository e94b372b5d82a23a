"""Transient-vs-real stage-failure classification.

The hand-run chains could not tell a dropped connection from a failed
benchmark — both left a dead stage in chain.log and a human decided
what to re-run.  This module encodes that judgment:

  **transient** (auto-retried through the RetryPolicy):
    - the process was *killed* — SIGKILL/SIGTERM/SIGHUP/SIGINT/SIGPIPE,
      as a negative returncode or the shell's 128+N form.  That is the
      dropped-connection / environment-reset signature: something
      outside the benchmark ended it.
    - the stage hit its declared timeout (a backend that never comes up and
      a compile that hangs present as this).
    - the stderr tail carries a known transport/backend marker
      (connection reset, backend init, DEADLINE_EXCEEDED, ...).

  **fatal** (stops the chain loudly):
    - crash signals — SIGSEGV/SIGABRT/SIGILL/SIGFPE/SIGBUS.  SIGILL in
      particular is the AOT machine-feature hazard (drand_tpu/aot.py):
      re-running cannot fix it, rebuilding the executable can.
    - any other non-zero exit: a Python traceback, a failed assertion,
      a bad config — a REAL benchmark failure a retry would only
      repeat (and whose repetition would corrupt the measurement
      ledger with a silently re-run stage).

The classifier is a pure function of (returncode, stderr tail,
timed-out flag) so the matrix is unit-testable without subprocesses
(tests/test_warm.py).
"""

from __future__ import annotations

import signal

TRANSIENT = "transient"
FATAL = "fatal"

# signals that mean "something outside the stage ended it" — retryable
_KILLED_SIGNALS = frozenset({
    signal.SIGKILL, signal.SIGTERM, signal.SIGHUP, signal.SIGINT,
    signal.SIGPIPE,
})
# signals that mean "the stage itself crashed" — a retry repeats it
_CRASH_SIGNALS = frozenset({
    signal.SIGSEGV, signal.SIGABRT, signal.SIGILL, signal.SIGFPE,
    signal.SIGBUS,
})

# lowercase substrings in the stderr tail that mark a transient
# transport/backend condition even when the stage exited non-zero on
# its own (e.g. a grpc UNAVAILABLE surfacing as a Python exception)
_TRANSIENT_MARKERS = (
    "connection reset", "connection refused", "connection closed",
    "broken pipe", "socket closed", "socket hang up",
    "temporarily unavailable", "timed out", "timeout exceeded",
    "deadline_exceeded", "deadline exceeded", "unavailable",
    "failed to initialize backend", "unable to initialize backend",
    "backend init", "backend_init",
    "transport failure", "rpc failed", "os error 104",
)


def _signal_name(num: int) -> str:
    try:
        return signal.Signals(num).name
    except ValueError:
        return f"signal {num}"


def classify_stage(returncode: int | None, stderr_tail: str = "",
                   timed_out: bool = False) -> tuple[str, str]:
    """Classify one failed stage attempt.  Returns (verdict, reason)
    where verdict is :data:`TRANSIENT` or :data:`FATAL` and reason is
    the one-line operator explanation recorded in the checkpoint and
    the decision log."""
    if timed_out:
        return TRANSIENT, "stage hit its declared timeout (killed)"
    rc = returncode if returncode is not None else -1
    sig = None
    if rc < 0:
        sig = -rc
    elif rc > 128 and rc <= 128 + 64:        # the shell's 128+N encoding
        sig = rc - 128
    if sig is not None:
        if sig in {int(s) for s in _CRASH_SIGNALS}:
            return FATAL, (f"stage crashed with {_signal_name(sig)} — a "
                           "retry would repeat it (SIGILL: rebuild the "
                           "AOT entry on this machine)")
        if sig in {int(s) for s in _KILLED_SIGNALS}:
            return TRANSIENT, (f"process killed by {_signal_name(sig)} "
                               "(dropped connection / environment reset pattern)")
        return TRANSIENT, f"process ended by {_signal_name(sig)}"
    tail = (stderr_tail or "").lower()
    for marker in _TRANSIENT_MARKERS:
        if marker in tail:
            return TRANSIENT, (f"rc={rc} with transient marker "
                               f"{marker!r} in stderr")
    return FATAL, (f"rc={rc} with no transient signature — a real "
                   "benchmark failure; fix it, then `warm resume`")
