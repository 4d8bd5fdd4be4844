"""What the readers of the program's counters share: what a counter gained
over the window, per restore and per byte of state.  None for a window with
no restore and for a program that has no such counter (the commits before
the one that added it)."""

from __future__ import annotations

from typing import Any, Optional


def per_restore_state_byte(ctx: Any, name: str) -> Optional[float]:
    after = ctx.obs_after["counters"].get(name)
    n = ctx.count("restore")
    if after is None or not n:
        return None
    gained = after - ctx.obs_before["counters"].get(name, 0)
    return gained / n / ctx.notes["state_bytes"]
