"""What the readers of a slab's path share: the bytes that the program's
device pack / unpack programs moved, one counter an element width
(``device_pack.bytes_w2``, ``..._w4``; ``device_unpack.bytes_w*``), and the
bytes of the slabs that took the host path (``slab.host_pack_bytes``,
``slab.host_unpack_bytes``), per save or restore and per byte of state.

None for a window with no such operation, and for a program that has none
of one side's counters (the commits before the one that added them); a
counter of a side that has others and was never raised reads 0."""

from __future__ import annotations

from typing import Any, Collection, Optional, Tuple

PACK = ("device_pack.bytes_w", "slab.host_pack_bytes", "take")
UNPACK = ("device_unpack.bytes_w", "slab.host_unpack_bytes", "restore")
NARROW = ("1", "2")  # element widths under float32's


def _share(ctx: Any, side: Tuple[str, str, str], names: Collection[str]) -> Optional[float]:
    by_width, host, op = side
    after, before = ctx.obs_after["counters"], ctx.obs_before["counters"]
    n = ctx.count(op)
    if not n or not any(k.startswith(by_width) or k == host for k in after):
        return None
    gained = sum(after.get(k, 0) - before.get(k, 0) for k in names)
    return gained / n / ctx.notes["state_bytes"]


def narrow_share(ctx: Any, side: Tuple[str, str, str]) -> Optional[float]:
    """Bytes a device program moved as 1- or 2-byte words ÷ bytes of state."""
    return _share(ctx, side, [side[0] + width for width in NARROW])


def host_share(ctx: Any, side: Tuple[str, str, str]) -> Optional[float]:
    """Bytes of the slabs that took the host path ÷ bytes of state."""
    return _share(ctx, side, [side[1]])


def members_mean(ctx: Any, span: str) -> Optional[float]:
    """Mean of the attr ``members`` over the window's spans of one name."""
    members = [s.attrs["members"] for s in ctx.spans if s.name == span and "members" in s.attrs]
    return sum(members) / len(members) if members else None
