"""Shared child-driver for the SIGKILL crash tests.

One implementation of spawn → watch stdout → kill-at-marker, used by
`tests/test_crash_recovery.py` (engineered kill point) and
`tests/test_crash_fuzz.py` (randomized kill timing), so the two cannot
drift: the killed-flag discipline (a child that finishes or dies on its
own is NOT a successful kill) and the silent-wedge watchdog (a child
that stops emitting lines is reaped, never hangs CI) live here.
"""

import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple


def spawn_fuzz_child(
    child_src: str, repo_root: str, extra_env: Dict[str, str]
) -> "subprocess.Popen[str]":
    """Spawn a crash-fuzz child with the shared env discipline (CPU
    backend) and stdout/stderr merged so tracebacks
    land in the marker stream — kept here so the fuzz tests cannot
    drift apart on spawn mechanics."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TSNP_REPO": repo_root,
        **extra_env,
    }
    return subprocess.Popen(
        [sys.executable, "-c", child_src],
        stdout=subprocess.PIPE,
        # tracebacks must land in the marker stream: a child that
        # crashes on its own is the interesting fuzz outcome, and
        # DEVNULL would discard the only diagnostic
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )


def kill_child_at(
    proc: "subprocess.Popen[str]",
    marker: str,
    kill_delay: float = 0.0,
    stop_markers: Tuple[str, ...] = (),
    wedge_timeout: float = 90.0,
) -> Tuple[bool, List[str]]:
    """Read ``proc``'s stdout until ``marker`` appears, wait
    ``kill_delay`` seconds, then SIGKILL it.

    Returns ``(killed, lines)`` — ``killed`` is True only when the kill
    was actually delivered at the marker; a child that printed a
    ``stop_markers`` line, exited on its own, or wedged silently
    returns False so callers fail loudly instead of mistaking a child
    crash for a successful kill.

    A watchdog reaps the child after ``wedge_timeout`` seconds of
    OUTPUT SILENCE (the deadline resets on every received line, so a
    slow-but-progressing child is never mistaken for a wedged one):
    ``for line in stdout`` blocks indefinitely on a silently wedged
    child and an in-loop deadline check would never run (the exact hang
    a crash harness exists to surface).
    """
    wedged = threading.Event()
    progress = [time.time()]  # [-1] = when the last line arrived
    # absolute cap: a LIVELOCKED child that keeps printing lines resets
    # the silence deadline forever; total runtime still has to end
    hard_deadline = time.time() + 4 * wedge_timeout

    def _watchdog() -> None:
        while (
            time.time() - progress[-1] < wedge_timeout
            and time.time() < hard_deadline
        ):
            if proc.poll() is not None:
                return
            time.sleep(0.25)
        wedged.set()
        proc.kill()

    watchdog = threading.Thread(target=_watchdog, daemon=True)
    watchdog.start()
    killed = False
    lines: List[str] = []
    assert proc.stdout is not None
    for line in proc.stdout:
        progress.append(time.time())
        lines.append(line.strip())
        if marker in line:
            time.sleep(kill_delay)
            proc.kill()  # SIGKILL: no cleanup of any kind runs
            killed = True
            break
        if any(s in line for s in stop_markers):
            break
    try:
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    # a watchdog firing AFTER the marker kill landed must not demote a
    # successful kill to a wedge (it can race into the kill_delay sleep)
    if wedged.is_set() and not killed:
        return False, lines + ["<wedged: watchdog reaped child>"]
    return killed, lines
