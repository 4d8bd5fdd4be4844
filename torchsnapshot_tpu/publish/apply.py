"""Live-weight applier: staged delta apply with a generation counter
and an atomic swap barrier.

``LiveWeights`` wraps a serving process's ``app_state`` (the standard
stateful/state-dict template).  An apply has two strictly separated
halves:

1. **Stage** (no lock, no mutation): for every leaf the plan touched,
   reconstruct the leaf's new bytes — current bytes as the basis,
   fetched chunks overlaid at their leaf offsets — and decode them into
   fresh arrays/objects.  Any failure here (bad fetch, template drift,
   a killed subscriber's in-flight poll) leaves the live state bitwise
   untouched: the next poll simply re-stages from the last complete
   generation.
2. **Swap** (under the generation lock): load every staged leaf into
   the app state and bump the generation.  Readers that wrap request
   handling in ``pinned()`` hold the same lock, so a request observes
   either the old generation or the new one for ALL leaves — never a
   torn mix of steps.

The basis rule is what makes deltas sound: a chunk the plan skipped is
bitwise-identical between the held and new records (same content key at
the same offset), so the CURRENT leaf bytes already hold its content.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .. import obs
from ..continuous.store import decode_leaf, encode_leaf
from ..flatten import flatten, inflate
from ..resilience.failpoints import failpoint
from .delta import DeltaPlan


class TemplateMismatchError(RuntimeError):
    """The publication record and the live app state disagree on the
    leaf set (strict mode)."""


class LiveWeights:
    """One serving process's swappable view of ``app_state``.  All
    mutation goes through ``apply``; readers bracket request handling
    with ``pinned()`` to get a torn-swap-free view."""

    def __init__(self, app_state: Dict[str, Any]) -> None:
        self._app_state = app_state
        self._lock = threading.RLock()
        self._generation = 0
        self._step: Optional[int] = None

    @property
    def generation(self) -> int:
        with self._lock:
            return self._generation

    @property
    def step(self) -> Optional[int]:
        with self._lock:
            return self._step

    @contextlib.contextmanager
    def pinned(self) -> Iterator[Tuple[Optional[int], int]]:
        """Hold the swap barrier for the duration of a request: yields
        ``(step, generation)``; no apply can commit while held."""
        with self._lock:
            yield (self._step, self._generation)

    def current_leaves(
        self,
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """``(manifest, flattened)`` of the live state — the apply
        basis and the subscriber's template view."""
        with self._lock:
            state_tree = {
                k: (v.state_dict() if hasattr(v, "state_dict") else v)
                for k, v in self._app_state.items()
            }
        return flatten(state_tree)

    def apply(
        self,
        record: Dict[str, Any],
        plan: DeltaPlan,
        fetched: Dict[Tuple[str, int], bytes],
        strict: bool = True,
    ) -> int:
        """Stage + swap one published step into the live state (see
        module docstring); returns the new generation.  ``fetched``
        maps ``(leaf, leaf_off) → verified chunk bytes`` for every
        fetch item in ``plan``."""
        with obs.span(
            "publish/apply", step=record["step"], fetched=len(fetched)
        ):
            staged = self._stage(record, plan, fetched, strict)
            # deterministic chaos hook: a subscriber dying here (after
            # staging, before the swap) must leave the live state at
            # its last complete generation
            failpoint("publish.subscriber.apply", step=record["step"])
            with self._lock:
                self._load(staged)
                self._generation += 1
                self._step = int(record["step"])
                obs.gauge(obs.PUBLISH_GENERATION).set(self._generation)
                return self._generation

    # -------------------------------------------------------- staging

    def _stage(
        self,
        record: Dict[str, Any],
        plan: DeltaPlan,
        fetched: Dict[Tuple[str, int], bytes],
        strict: bool,
    ) -> Dict[str, Any]:
        manifest, flattened = self.current_leaves()
        rec_leaves: Dict[str, Any] = record["leaves"]
        missing = [p for p in flattened if p not in rec_leaves]
        extra = [p for p in rec_leaves if p not in flattened]
        if (missing or extra) and strict:
            raise TemplateMismatchError(
                f"publication record and live template disagree: "
                f"record lacks {len(missing)} template leaves "
                f"(e.g. {missing[:3]}), template lacks {len(extra)} "
                f"record leaves (e.g. {extra[:3]}); pass strict=False "
                f"to apply the intersection"
            )
        if extra:
            obs.counter(obs.PUBLISH_LEAVES_SKIPPED).inc(len(extra))
        touched = {item.leaf for item in plan.fetches}
        touched.update(
            p for p in plan.full_leaves if p in flattened
        )
        by_leaf: Dict[str, List] = {}
        for item in plan.fetches:
            by_leaf.setdefault(item.leaf, []).append(item)
        staged: Dict[str, Any] = {}
        for path in sorted(touched):
            if path not in flattened:
                continue  # counted above (non-strict extra)
            leaf_doc = rec_leaves[path]
            win_lo, win_hi = plan.windows.get(
                path, (0, int(leaf_doc["size"]))
            )
            buf = bytearray(win_hi - win_lo)
            if path not in plan.full_leaves:
                # delta basis: the current leaf's bytes hold every
                # skipped chunk's content (key-identical by plan)
                _rec, view = encode_leaf(flattened[path])
                if view.nbytes != len(buf):
                    raise TemplateMismatchError(
                        f"live leaf {path!r} holds {view.nbytes} bytes "
                        f"but the plan window is {len(buf)} — the held "
                        f"generation does not match its record"
                    )
                buf[:] = view
            for item in by_leaf.get(path, ()):
                data = fetched[(item.leaf, item.leaf_off)]
                # window-relative placement, edges sliced (chunks are
                # fetched whole so their content keys verify)
                dst_lo = max(item.leaf_off, win_lo) - win_lo
                src_lo = max(win_lo - item.leaf_off, 0)
                src_hi = min(item.leaf_off + item.nbytes, win_hi) - (
                    item.leaf_off
                )
                buf[dst_lo : dst_lo + (src_hi - src_lo)] = data[
                    src_lo:src_hi
                ]
            staged[path] = self._decode_window(leaf_doc, bytes(buf), path)
        return staged

    def _decode_window(
        self, leaf_doc: Dict[str, Any], data: bytes, path: str
    ) -> Any:
        """Decode a (possibly window-narrowed) leaf byte stream into a
        fresh value, shaped like the LIVE leaf for sharded windows."""
        if leaf_doc.get("kind") == "prim":
            # value inlined in the record (snapshot-published
            # primitives) — no byte stream at all
            from ..manifest import PrimitiveEntry

            return PrimitiveEntry(
                type=str(leaf_doc["ptype"]),
                readable=str(leaf_doc["v"]),
                replicated=True,
            ).get_value()
        if leaf_doc.get("kind") != "array":
            return decode_leaf(leaf_doc, data)
        dtype_rec = {
            "kind": "array",
            "dtype": leaf_doc["dtype"],
            "shape": [-1] + [int(d) for d in leaf_doc["shape"][1:]],
            "size": len(data),
        }
        arr = decode_leaf(dtype_rec, data)
        if not leaf_doc["shape"]:
            arr = arr.reshape(())
        return arr

    # ----------------------------------------------------------- swap

    def _load(self, staged: Dict[str, Any]) -> None:
        if not staged:
            return
        manifest, flattened = self.current_leaves()
        merged = {
            p: staged.get(p, flattened[p]) for p in flattened
        }
        inflated = inflate(manifest, merged)
        for k, stateful in self._app_state.items():
            if hasattr(stateful, "load_state_dict"):
                stateful.load_state_dict(inflated[k])
            else:
                self._app_state[k] = inflated[k]
