"""Preemption-grade continuous checkpointing: sub-second in-RAM peer
deltas with a measured recovery-time objective.

Spot/preemptible fleets should lose ONE step, not the minutes since
the last durable snapshot.  This subsystem composes pieces the library
already trusts — content-addressed chunk deltas (cas/), budgeted
background I/O (scheduler), peer fast roots and the write-back
promoter (tier/), topology-aware placement (topology/), the SIGTERM
grace-window hook (resilience/preemption.py) — into an always-on
per-step loop:

- after every training step, the CHANGED chunks of the flattened state
  tree replicate to a peer host's RAM over the fast-root path (no
  durable round-trip), marker-last so a peer store always names a
  complete step;
- every N steps the in-RAM store promotes to a durable mirror through
  ``tier/promoter.py`` (pinned-HEAD marker-last commit);
- a preempted or killed host restores from its peer in seconds
  (``recover_state`` / ``ContinuousCheckpointer.restore_latest``),
  falling back to the durable mirror when the peer is gone too —
  graceful degradation, never a wedge.

Public surface: ``ContinuousCheckpointer`` (loop.py),
``recover_state`` (recover.py), ``ContinuousStore`` (store.py),
``summary_block`` (doctor/flight-record rollup).  Knobs: CONTINUOUS,
CONTINUOUS_GRACE_S (knobs.py).  See
docs/preemption.md.
"""

from __future__ import annotations

from .loop import ContinuousCheckpointer, summary_block  # noqa: F401
from .recover import (  # noqa: F401
    TemplateMismatchError,
    recover_state,
)
from .store import (  # noqa: F401
    HEAD_FNAME,
    ContinuousStore,
    step_manifest_path,
)

__all__ = [
    "ContinuousCheckpointer",
    "ContinuousStore",
    "HEAD_FNAME",
    "TemplateMismatchError",
    "recover_state",
    "step_manifest_path",
    "summary_block",
]
