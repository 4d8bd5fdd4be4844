"""Reviewed snaplint suppressions.  Every entry names the pass, the
file, the enclosing def/class qualname, and — mandatorily — a written
justification explaining why the finding is acceptable THERE.  The
driver rejects entries whose justification is blank or token-length
(core.validate_allowlist); an entry matching nothing prints a staleness
warning so dead suppressions get cleaned up.

Etiquette (docs/static_analysis.md): an allowlist entry is a reviewed
decision, not an escape hatch.  Prefer fixing the finding; allowlist
only when the flagged shape IS the contract (e.g. a CLI probe whose
output literally reports "this read failed"), and say so in prose a
future reviewer can re-evaluate.
"""

from __future__ import annotations

from typing import Tuple

from .core import Allow

ALLOWLIST: Tuple[Allow, ...] = (
    Allow(
        pass_id="exception-hygiene",
        file="torchsnapshot_tpu/__main__.py",
        context="_cmd_tiers",
        justification=(
            "The tiers CLI probes each step's metadata in BOTH tiers to "
            "classify residency; any failure (absent, aborted, corrupt, "
            "unreachable backend) IS the datum being measured and is "
            "reported in the command's status column — logging here "
            "would spam stderr once per uncommitted step on every run."
        ),
    ),
    Allow(
        pass_id="retry-discipline",
        file="torchsnapshot_tpu/coordination.py",
        context="FileCoordinator._kv_get_impl",
        justification=(
            "This loop IS the blocking-get KV primitive itself — a "
            "fixed-interval existence poll of a shared-filesystem key, "
            "not a backoff retry of a fallible op.  resilience.retry "
            "wraps ops that FAIL transiently; a not-yet-written key is "
            "the wait's normal pending state, and abort-awareness for "
            "this wait is layered above it in Coordinator.kv_get."
        ),
    ),
    Allow(
        pass_id="retry-discipline",
        file="torchsnapshot_tpu/coordination.py",
        context="kv_watch",
        justification=(
            "Same shape as FileCoordinator._kv_get_impl: kv_watch IS "
            "the change-wait KV primitive (value absent or unchanged "
            "is the wait's normal pending state, kv_try_get never "
            "raises into the loop), not a backoff retry of a fallible "
            "op — and its deadline is the caller's poll interval, so "
            "the retry module's shared-progress window would cap the "
            "WRONG budget."
        ),
    ),
    Allow(
        pass_id="retry-discipline",
        file="torchsnapshot_tpu/snapshot.py",
        context="_recovery_kv_get",
        justification=(
            "The takeover recovery protocol's KV wait: a fixed-interval "
            "existence poll (kv_try_get never raises into the loop; an "
            "absent key is the wait's normal pending state), same "
            "primitive shape as FileCoordinator._kv_get_impl.  It "
            "cannot route through the scoped Coordinator.kv_get because "
            "that wait re-raises RankDeadError on the ALREADY-dead set "
            "the recovery is recovering FROM — this loop's whole job is "
            "to keep waiting through known deaths and raise only on NEW "
            "ones, which it checks each tick via the monitor."
        ),
    ),
    Allow(
        pass_id="retry-discipline",
        file="torchsnapshot_tpu/tier/promoter.py",
        context="Promoter._await_done_keys",
        justification=(
            "The tier done-handshake wait: a fixed-interval existence "
            "poll of each rank's done-key (kv_try_get never raises into "
            "the loop; absence is the normal pending state while the "
            "peer's copy job runs).  resilience.retry wraps ops that "
            "FAIL transiently and would cap the wrong budget here; this "
            "loop's exits are its own protocol facts — key landed, "
            "poison observed, peer declared dead by the liveness "
            "monitor, or the handshake deadline."
        ),
    ),
    Allow(
        pass_id="retry-discipline",
        file="torchsnapshot_tpu/obs/aggregate.py",
        context="collect_and_merge",
        justification=(
            "Bounded best-effort poll for a peer's flight-record "
            "payload AFTER the commit barrier already proved the peer "
            "finished: kv_try_get returns None (never raises) while KV "
            "propagation trails the barrier, so there is no fallible "
            "op for resilience.retry to classify — and a missing "
            "payload is an accepted outcome (recorded as a missing "
            "rank), not a failure to retry harder."
        ),
    ),
    # The dispatch_staging and _read_one_inner entries that used to sit
    # here are RETIRED: the executor cross-task handoff their prose
    # asserted is now machine-checked every run by the interprocedural
    # closure-domain sanction (summaries.closure_sanction via the
    # resource-pairing summary hook) — a debit in a pipeline closure is
    # accepted only while the enclosing executor's domain provably
    # contains the matching credit on the same receiver, so the rename
    # that would have silently invalidated these justifications now
    # fails the lint instead.
    Allow(
        pass_id="resource-pairing",
        file="torchsnapshot_tpu/scheduler.py",
        context="_execute_read_pipelines",
        justification=(
            "Read-side admission debits hand the pipeline to read_one "
            "tasks; the matching credit fires at consume completion in "
            "a later iteration of the same executor loop (or its "
            "cancellation sweep) — a cross-ITERATION pairing inside "
            "one function body, which stays outside the closure-domain "
            "sanction (that proof covers debits in NESTED defs; these "
            "sit in the executor body itself).  Interprocedural "
            "evidence bounding the risk: the effect-escape pass "
            "verifies the budget verb family is two-sided package-wide "
            "and that this function's own summary carries both "
            "debit and credit effects on the same `budget` receiver "
            "(tools/lint/summaries.py res effects); path-exactness "
            "across loop iterations is asserted end-to-end by the "
            "scheduler fuzz and take-invariant suites.  The concurrent "
            "half of the old prose (\"no second flow can interleave "
            "debit and credit\") is RETIRED from this justification: "
            "execution-domain inference (tools/lint/domains.py) now "
            "machine-proves the executor body is event-loop-confined, "
            "so a refactor that moved the credit onto a worker thread "
            "would trip the domain-crossing pass instead of silently "
            "invalidating this entry."
        ),
    ),
    Allow(
        pass_id="resource-pairing",
        file="torchsnapshot_tpu/storage/stripe.py",
        context="striped_write",
        justification=(
            "The abort handler increments STRIPE_ABORTS before the "
            "shielded _abort_quiet(handle) so a second cancellation "
            "arriving during the shield cannot lose the count of an "
            "abort that actually ran.  The CFG's conservative "
            "exception edge out of the increment is vacuous: "
            "Counter.inc is a lock-protected integer add that cannot "
            "raise, so no real path reaches exit without the abort."
        ),
    ),
    Allow(
        pass_id="async-blocking",
        file="torchsnapshot_tpu/scheduler.py",
        context="_execute_write_pipelines",
        justification=(
            "task.result() here is asyncio.Task.result() on members of "
            "the `done` set returned by asyncio.wait — a completed-"
            "future accessor that returns (or re-raises) immediately, "
            "not a concurrent.futures blocking wait.  The lexical "
            "shape is indistinguishable, so the sanctioned idiom is "
            "recorded here."
        ),
    ),
    Allow(
        pass_id="async-blocking",
        file="torchsnapshot_tpu/scheduler.py",
        context="_execute_read_pipelines",
        justification=(
            "Same asyncio.wait done-set accessor idiom as the write "
            "executor: task.result() on tasks asyncio.wait already "
            "reported complete returns immediately and never parks the "
            "event loop."
        ),
    ),
    # Concurrency-layer entries (lockset-race / domain-crossing).
    # These three are happens-before edges or single-threaded phases
    # the lockset model deliberately does not track — each names the
    # ordering fact a reviewer must re-check before touching the code.
    Allow(
        pass_id="lockset-race",
        file="torchsnapshot_tpu/snapshot.py",
        context="PendingSnapshot._complete_snapshot",
        justification=(
            "_exc is written only on the tsnp-commit thread inside "
            "_complete_snapshot; the caller domain reads it only in "
            "wait(), strictly AFTER self._thread.join() — a "
            "Thread.join happens-before edge the lockset model cannot "
            "see.  A lock here would serialize nothing real: the two "
            "domains never overlap in time.  Re-check if _exc ever "
            "grows a reader that does not join first (e.g. a "
            "non-blocking poll_error accessor)."
        ),
    ),
    Allow(
        pass_id="domain-crossing",
        file="torchsnapshot_tpu/knobs.py",
        context="_override",
        justification=(
            "_OVERRIDES is the test-fixture override map: it is "
            "mutated only by the override_* context managers, which "
            "tests enter in single-threaded setup before spawning any "
            "worker (and exit after joining them); every production "
            "path only READS it via _get.  The multi-domain reach the "
            "pass sees is those production readers — there is no "
            "concurrent writer to race them.  Re-check if any "
            "override_* call ever moves inside a running job."
        ),
    ),
    Allow(
        pass_id="domain-crossing",
        file="torchsnapshot_tpu/utils/checksums.py",
        context="_shift_matrix",
        justification=(
            "_SHIFT_BY_POW2_BYTES is an append-only memo with a "
            "deliberate lock-free fast path on the per-chunk "
            "crc-combine hot loop: a row is fully constructed before "
            "being appended under _SHIFT_LOCK and is never mutated "
            "after, so a racy reader sees either the complete row or "
            "a miss that takes the locked slow path and re-checks.  "
            "Guarding the read would put a lock acquisition on every "
            "chunk of every snapshot for zero safety gain."
        ),
    ),
    Allow(
        pass_id="protocol-lockstep",
        file="torchsnapshot_tpu/snapshot.py",
        context="Snapshot._repair_degraded_impl",
        justification=(
            "Degraded-snapshot repair is a deliberately SINGLE-PROCESS "
            "ops tool (SnapshotManager.repair gates it to rank 0; the "
            "dead rank it heals is by definition not running): it "
            "re-writes lost payloads from continuous-store mirrors and "
            "then rewrites the already-committed marker strictly last, "
            "with no fleet to synchronize with.  The pass's "
            "sync-point-before-marker rule guards COLLECTIVE commits; "
            "requiring one here would force a barrier into a recovery "
            "path that must work precisely when peers are gone.  "
            "Crash-safety holds without it: the marker write is atomic "
            "and a crash mid-repair leaves the previous still-committed "
            "(still-degraded) marker in place."
        ),
    ),
)
