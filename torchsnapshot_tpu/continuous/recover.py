"""Recovery: restore a rank's state from the freshest reachable
continuous store, in seconds.

Recovery is FRESHEST-first, measured, not assumed: every source's HEAD
is probed first (one tiny read each), and full restores are attempted
in descending step order — ladder position (local → peers → durable)
only breaks ties.  Individual targets are ALLOWED to lag (a failed
replication leaves a store at its older complete step), so "local
before peer" as a blind order could silently lose more than the
one-step bound the loop guarantees; probing HEADs first costs
milliseconds and restores the bound.  Every read runs under normal
exception handling: a dead host's unreachable root, a mid-write torn
store (no HEAD advance — marker-last makes torn unobservable), or a
corrupt chunk (content keys fail closed) all mean "next candidate",
so recovery degrades gracefully and NEVER wedges; when no source is
usable the caller gets None — a cold start, exactly like
``SnapshotManager.restore_latest``.

The measured wall time of each successful recovery lands in the
``continuous.restore_s`` histogram — the recovery-time objective the
chaos suite asserts on.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import obs
from ..flatten import flatten, inflate
from .store import ContinuousStore

logger = logging.getLogger(__name__)


class TemplateMismatchError(KeyError):
    """The store's leaves don't cover the template (strict mode).
    Deliberately NOT part of the source-ladder degradation: the same
    template mismatches every source identically, so swallowing it
    would turn a caller bug into a silent cold start."""


def _apply_leaves(
    app_state: Dict[str, Any],
    leaves: Dict[str, Any],
    strict: bool,
) -> None:
    """Load recovered leaves back into the app-state template (the
    standard restore contract: structure comes from the template,
    values from the store)."""
    state_tree = {
        k: (v.state_dict() if hasattr(v, "state_dict") else v)
        for k, v in app_state.items()
    }
    manifest, flattened = flatten(state_tree)
    missing = [p for p in flattened if p not in leaves]
    extra = [p for p in leaves if p not in flattened]
    if missing and strict:
        raise TemplateMismatchError(
            f"continuous store is missing {len(missing)} leaves the "
            f"template expects (e.g. {missing[:3]}); pass strict=False "
            f"to keep template values for them"
        )
    if extra:
        logger.warning(
            "continuous store carries %d leaves the template does not "
            "(e.g. %s); ignoring them", len(extra), extra[:3],
        )
    merged = {
        p: leaves.get(p, flattened[p]) for p in flattened
    }
    inflated = inflate(manifest, merged)
    for k, stateful in app_state.items():
        if hasattr(stateful, "load_state_dict"):
            stateful.load_state_dict(inflated[k])
        else:
            app_state[k] = inflated[k]


def recover_state(
    app_state: Dict[str, Any],
    local: Optional[str] = None,
    peers: Sequence[str] = (),
    durable: Optional[str] = None,
    strict: bool = True,
) -> Optional[Dict[str, Any]]:
    """Restore ``app_state`` from the freshest reachable continuous
    store (see module docstring).  ``local``/``peers``/``durable`` are
    STORE roots (already rank-namespaced — the checkpointer's
    ``restore_latest`` builds them).  Returns
    ``{"step", "source", "root", "seconds"}`` or None when no source
    holds a complete step (cold start)."""
    sources: List[Tuple[str, str]] = []
    if local:
        sources.append((local, "local"))
    sources.extend((p, "peer") for p in peers)
    if durable:
        sources.append((durable, "durable"))
    m_by_source = {
        "local": obs.CONTINUOUS_RESTORES_FROM_LOCAL,
        "peer": obs.CONTINUOUS_RESTORES_FROM_PEER,
        "durable": obs.CONTINUOUS_RESTORES_FROM_DURABLE,
    }
    with obs.span("continuous/recover", sources=len(sources)):
        # phase 1: probe every source's HEAD (one tiny verified read
        # each) so the full restore can go FRESHEST-first — ladder
        # position is only the tiebreak
        candidates: List[Tuple[int, int, str, str, Dict[str, Any]]] = []
        for idx, (root, kind) in enumerate(sources):
            store = ContinuousStore(root)
            try:
                head = store.read_head()
            except Exception as e:  # noqa: BLE001 — unusable source
                logger.warning(
                    "continuous recovery: HEAD probe of %s store %r "
                    "failed (%r); skipping it", kind, root, e,
                )
                continue
            finally:
                store.sync_close()
            if head is None:
                logger.info(
                    "continuous recovery: %s store %r has no complete "
                    "step", kind, root,
                )
                continue
            candidates.append((int(head["step"]), idx, root, kind, head))
        candidates.sort(key=lambda c: (-c[0], c[1]))
        # phase 2: restore from the newest candidate that fully reads
        for _step_hint, _idx, root, kind, head in candidates:
            t0 = time.monotonic()
            store = ContinuousStore(root)
            try:
                step, leaves = store.read_state(head)
                _apply_leaves(app_state, leaves, strict=strict)
            except TemplateMismatchError:
                raise
            except Exception as e:  # noqa: BLE001 — degrade candidate
                # by candidate: an unreachable peer or torn/corrupt
                # store is the scenario this ladder exists for
                logger.warning(
                    "continuous recovery from %s store %r failed "
                    "(%r); trying next candidate", kind, root, e,
                )
                continue
            finally:
                store.sync_close()
            seconds = time.monotonic() - t0
            obs.counter(m_by_source[kind]).inc()
            obs.histogram(obs.CONTINUOUS_RESTORE_S).observe(seconds)
            logger.info(
                "continuous recovery: step %d from %s store %r in "
                "%.3fs", step, kind, root, seconds,
            )
            return {
                "step": step,
                "source": kind,
                "root": root,
                "seconds": seconds,
            }
    return None
