"""Tier-1 wiring for the snaplint suite (tools/lint): the repo must be
clean under all sixteen passes (modulo the reviewed allowlist and the
baseline ratchet), each pass must actually detect its bug class (a
checker that can't fail is no check), and the allowlist/baseline
machinery must enforce its contracts (written justifications; finding
counts only ratchet down).  The CFG substrate the flow-sensitive
passes ride on has its own edge-exactness suite in test_lint_cfg.py;
the interprocedural substrate (call graph, summaries, cache) and the
three passes built on it are covered in test_lint_interproc.py."""

import json
import os
import sys
import textwrap
import time

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from tools.lint import (  # noqa: E402
    ALL_PASSES,
    ALLOWLIST,
    Allow,
    LintConfigError,
    check_ratchet,
    load_baseline,
    run_repo,
    run_source,
    save_baseline,
    validate_allowlist,
)
from tools.lint.cli import DEFAULT_BASELINE, main, repo_summary  # noqa: E402

_BY_ID = {p.pass_id: p for p in ALL_PASSES}


def _run(pass_id, src, filename="torchsnapshot_tpu/example.py"):
    return run_source(
        textwrap.dedent(src), filename, [_BY_ID[pass_id]]
    )


# ------------------------------------------------------- repo-wide gate


def test_repo_is_clean():
    """THE gate: zero unbaselined findings repo-wide under ALL
    sixteen passes — flow-sensitive, interprocedural and concurrency ones
    included.  New findings must be fixed or allowlisted with a
    written justification — see docs/static_analysis.md.  Also the
    time budget: the full-repo run (CFG construction, call graph,
    summaries included) must stay under 10s of this process's CPU, or
    the lint stops being something every test run can afford (CPU
    seconds, not the wall's: other test workers share the box)."""
    t0 = time.process_time()
    result = run_repo(
        _REPO_ROOT,
        ALL_PASSES,
        allowlist=ALLOWLIST,
        baseline=load_baseline(DEFAULT_BASELINE),
    )
    elapsed = time.process_time() - t0
    assert result.files_scanned > 50  # the scan actually covered the repo
    assert [f.render() for f in result.unbaselined] == []
    # every allowlist entry still matches something (no stale entries)
    assert [
        f"{a.pass_id}:{a.file}:{a.context}" for a in result.unused_allows
    ] == []
    assert elapsed < 10.0, f"full-repo lint took {elapsed:.1f} CPU-s (budget 10)"


def test_flow_sensitive_and_interproc_passes_registered():
    """The CFG passes AND the three interprocedural passes are wired
    into the one pass tuple the repo gate, the CLI and the lint
    rollup all share — dropping one in a refactor must fail here, not
    silently shrink coverage."""
    ids = {p.pass_id for p in ALL_PASSES}
    assert {
        "async-blocking",
        "resource-pairing",
        "kv-hygiene",
        "metric-registry",
        "protocol-lockstep",
        "kv-matching",
        "effect-escape",
        "lockset-race",
        "lock-order",
        "domain-crossing",
    } <= ids
    assert len(ALL_PASSES) == 16
    # and the lint rollup (repo_summary) reports the roster
    s = repo_summary(_REPO_ROOT)
    assert set(s["passes"]) == ids


def test_repo_summary_timings_and_cache_stats():
    """The lint rollup's cost attribution: per-pass wall time
    for all sixteen passes and the summary-cache hit/miss split, with
    hits+misses covering every scanned file (so a cache regression is
    visible as a miss-count spike, not just a slower wall time)."""
    s = repo_summary(_REPO_ROOT)
    if s["summary_cache"]["misses"]:
        # first-ever run on this checkout: warm the cache, then the
        # second run over the unchanged tree must hit everywhere
        s = repo_summary(_REPO_ROOT)
    # every pass gets a timing, plus the shared interprocedural
    # substrate (call graph + summaries) under its own key — charging
    # it to whichever ProjectPass ran first would misdirect the cost
    # attribution
    assert set(s["timings_ms"]) == {p.pass_id for p in ALL_PASSES} | {
        "interproc-substrate"
    }
    assert all(t >= 0 for t in s["timings_ms"].values())
    cache = s["summary_cache"]
    assert cache["misses"] == 0
    assert cache["hits"] == s["files_scanned"]


def test_cli_main_clean_and_json(capsys):
    assert main([]) == 0
    capsys.readouterr()
    assert main(["--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["ok"] is True and data["unbaselined"] == []


def test_repo_summary_shape():
    s = repo_summary(_REPO_ROOT)
    assert s["unbaselined"] == 0
    assert isinstance(s["unbaselined_by_pass"], dict)


# ---------------------------------------------------- collective-safety


def test_collective_under_rank_branch_flagged():
    findings = _run(
        "collective-safety",
        """
        def commit(coord):
            if coord.rank == 0:
                coord.barrier()
        """,
    )
    assert len(findings) == 1
    assert "barrier" in findings[0].message
    assert findings[0].context == "commit"


def test_collective_in_else_and_elif_flagged():
    findings = _run(
        "collective-safety",
        """
        def commit(coord, rank):
            if rank != 0:
                pass
            elif rank == 1:
                coord.kv_exchange("k", "v")
            else:
                coord.all_gather_object(1)
        """,
    )
    assert len(findings) == 2


def test_collective_outside_branch_clean():
    findings = _run(
        "collective-safety",
        """
        def commit(coord, metadata):
            coord.barrier()
            if coord.rank == 0:
                storage.sync_write(metadata)  # rank-0 WORK is fine
            coord.barrier()
        """,
    )
    assert findings == []


def test_rank_conditional_ternary_argument_clean():
    # broadcast_object runs on ALL ranks; only its argument is
    # rank-conditional — the sanctioned manager.py pattern
    findings = _run(
        "collective-safety",
        """
        def restore_latest(self):
            step = self._coord.broadcast_object(
                self.latest_step() if self._coord.rank == 0 else None,
                src=0,
            )
            return step
        """,
    )
    assert findings == []


def test_rank_conditional_kv_ops_clean():
    # explicit-key KV is the sanctioned asymmetric-protocol pattern
    # (coordination.py _barrier_impl itself is built on it)
    findings = _run(
        "collective-safety",
        """
        def _barrier_impl(self, name):
            self.kv_set(f"{name}/arrive/{self._rank}", "1")
            if self._rank == 0:
                for r in range(self._world):
                    self.kv_get(f"{name}/arrive/{r}")
                self.kv_set(f"{name}/depart", "1")
            else:
                self.kv_get(f"{name}/depart")
        """,
    )
    assert findings == []


def test_collective_after_rank_gate_flagged():
    findings = _run(
        "collective-safety",
        """
        def gc(self):
            if self._coord.rank != 0:
                return
            self._coord.barrier()
        """,
    )
    assert len(findings) == 1
    assert "early exit" in findings[0].message


def test_collective_after_rank_gate_inside_with_flagged():
    # the gate sits inside `with log_event(...)`: divergence must
    # propagate through linear containers
    findings = _run(
        "collective-safety",
        """
        def gc(self):
            with log_event(Event("gc")):
                if self._coord.rank != 0:
                    return
                self._coord.barrier()
        """,
    )
    assert len(findings) == 1


def test_collective_in_ternary_branch_flagged():
    # `coord.barrier() if rank == 0 else None` calls the collective on
    # rank 0 only — the IfExp form of the same deadlock
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank):
            x = coord.barrier() if rank == 0 else None
            return x
        """,
    )
    assert len(findings) == 1


def test_collective_behind_short_circuit_flagged():
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank):
            if rank == 0 and coord.barrier():
                pass
            ok = rank != 0 or coord.kv_exchange("k", "v")
            return ok
        """,
    )
    assert len(findings) == 2


def test_collective_before_rank_in_boolop_clean():
    # the collective operand evaluates UNconditionally here
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank):
            ok = coord.barrier() and rank == 0
            return ok
        """,
    )
    assert findings == []


def test_rank_gated_return_inside_loop_flagged():
    # a return inside a loop leaves the whole function: collectives
    # after the loop deadlock too (continue/break must NOT propagate)
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank, items):
            for it in items:
                if rank != 0:
                    return
            coord.barrier()
        """,
    )
    assert len(findings) == 1


def test_rank_gate_in_elif_chain_flagged():
    # `elif rank != 0: return` is an If nested in the outer If's
    # orelse — divergence must propagate out of non-rank branches
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank, step):
            if step is None:
                prepare()
            elif rank != 0:
                return
            coord.barrier()
        """,
    )
    assert len(findings) == 1


def test_rank_gate_nested_in_plain_if_flagged():
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank, retry):
            if retry:
                if rank == 0:
                    return
            coord.barrier()
        """,
    )
    assert len(findings) == 1


def test_rank_gate_in_try_else_flagged():
    # try/else runs whenever the body completes — a rank gate there
    # diverges everything after the try statement
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank):
            try:
                x = prepare()
            except OSError:
                x = None
            else:
                if rank != 0:
                    return
            coord.barrier()
        """,
    )
    assert len(findings) == 1


def test_rank_gated_continue_dies_at_loop_boundary():
    findings = _run(
        "collective-safety",
        """
        def f(coord, rank, items):
            for it in items:
                if rank != 0:
                    continue
                publish(it)
            coord.barrier()
        """,
    )
    assert findings == []


def test_collective_in_nested_function_not_flagged():
    # a closure's body runs when CALLED — the lexical analysis stops at
    # function boundaries (documented false-negative, pinned here)
    findings = _run(
        "collective-safety",
        """
        def setup(coord):
            if coord.rank == 0:
                def job():
                    coord.barrier()
                return job
        """,
    )
    assert findings == []


# ------------------------------------------------------ lock-discipline


def test_open_under_lock_flagged():
    findings = _run(
        "lock-discipline",
        """
        def save(self, path):
            with self._lock:
                with open(path, "w") as f:
                    f.write("x")
        """,
    )
    assert len(findings) == 1
    assert "open" in findings[0].message


def test_storage_io_and_barrier_under_lock_flagged():
    findings = _run(
        "lock-discipline",
        """
        def promote(self, storage, coord):
            with _STATE_LOCK:
                storage.sync_write(io)
                coord.barrier()
        """,
    )
    assert {f.message.split("'")[1] for f in findings} == {
        "sync_write", "barrier",
    }


def test_async_with_lock_flagged():
    findings = _run(
        "lock-discipline",
        """
        async def drain(self):
            async with self._lock:
                await self.storage.sync_read(io)
                time.sleep(1)
        """,
    )
    assert len(findings) == 2


def test_fast_lock_body_clean():
    findings = _run(
        "lock-discipline",
        """
        def inc(self, n=1):
            with self._lock:
                self._value += n
        """,
    )
    assert findings == []


def test_nested_locks_report_each_call_once():
    findings = _run(
        "lock-discipline",
        """
        def f(self, path):
            with self._lock:
                with self._other_lock:
                    open(path)
        """,
    )
    assert len(findings) == 1


def test_lock_like_name_needs_word_boundary():
    # `clock`/`blocked` merely CONTAIN "lock" — not locks; `_REGISTRY_LOCK`
    # and `self.lock` are
    findings = _run(
        "lock-discipline",
        """
        def timed(self, path):
            with self.clock:
                open(path)

        def guarded(self, path):
            with _REGISTRY_LOCK:
                open(path)
        """,
    )
    assert len(findings) == 1
    assert findings[0].context == "guarded"


def test_nested_def_under_lock_clean():
    # defining a closure under a lock is fine — its body executes
    # elsewhere (the _csrc lazy-build pattern)
    findings = _run(
        "lock-discipline",
        """
        def load(self):
            with _lock:
                def _fresh(path):
                    with open(path) as f:
                        return f.read()
                self._loader = _fresh
        """,
    )
    assert findings == []


def test_acquire_without_release_flagged():
    findings = _run(
        "lock-discipline",
        """
        def leak(self):
            self._lock.acquire()
            do_work()
        """,
    )
    assert len(findings) == 1
    assert "release" in findings[0].message


def test_blocking_with_item_after_lock_flagged():
    # `with self._lock, open(p) as f:` — open() runs while the lock is
    # already held; later with-items are part of the critical section
    findings = _run(
        "lock-discipline",
        """
        def save(self, path):
            with self._lock, open(path) as f:
                f.read()
        """,
    )
    assert len(findings) == 1
    assert "open" in findings[0].message


def test_with_item_before_lock_clean():
    # items BEFORE the lock item evaluate lock-free
    findings = _run(
        "lock-discipline",
        """
        def save(self, path):
            with open(path) as f, self._lock:
                self._cache = f
        """,
    )
    assert findings == []


def test_acquire_with_release_clean():
    findings = _run(
        "lock-discipline",
        """
        def ok(self):
            self._lock.acquire()
            try:
                do_work()
            finally:
                self._lock.release()
        """,
    )
    assert findings == []


# ---------------------------------------------------- exception-hygiene


@pytest.mark.parametrize(
    "handler",
    ["except:", "except BaseException:", "except Exception:"],
)
def test_silent_swallow_flagged(handler):
    findings = _run(
        "exception-hygiene",
        f"""
        def f():
            try:
                work()
            {handler}
                pass
        """,
    )
    assert len(findings) == 1


def test_narrow_pass_only_clean():
    findings = _run(
        "exception-hygiene",
        """
        def f():
            try:
                work()
            except (OSError, ValueError):
                pass
        """,
    )
    assert findings == []


@pytest.mark.parametrize(
    "body",
    [
        "raise",  # re-raise
        "self._exc = e",  # captured for later re-raise
        "errors.append(e)",  # handed to state
        "callback(exc=e)",  # handed off via keyword argument
        "logger.exception('boom')",  # logged
        "obs.swallowed_exception('site', e)",  # sanctioned one-liner
        "obs.counter('x').inc()",  # counted
    ],
)
def test_baseexception_with_escape_clean(body):
    findings = _run(
        "exception-hygiene",
        f"""
        def f(self):
            try:
                work()
            except BaseException as e:
                {body}
        """,
    )
    assert findings == []


def test_escape_inside_nested_def_does_not_count():
    # a raise/log inside a closure only runs if the closure is called —
    # it is no escape for the handler itself
    findings = _run(
        "exception-hygiene",
        """
        def f(self):
            try:
                work()
            except BaseException:
                def report():
                    raise ValueError("never runs")
        """,
    )
    assert len(findings) == 1


def test_baseexception_without_escape_flagged():
    findings = _run(
        "exception-hygiene",
        """
        def f(self):
            try:
                work()
            except BaseException as e:
                self.status = "failed"
        """,
    )
    assert len(findings) == 1
    assert "BaseException" in findings[0].message


# -------------------------------------------------------- knob-registry


@pytest.mark.parametrize(
    "expr",
    [
        "os.environ.get('TORCHSNAPSHOT_TPU_TRACE')",
        "os.environ['TORCHSNAPSHOT_TPU_TRACE']",
        "os.getenv('TORCHSNAPSHOT_TPU_TRACE', '0')",
        "os.environ.setdefault('TORCHSNAPSHOT_TPU_TRACE', '1')",
        "os.environ.get('TSNP_S3_ENDPOINT_URL')",
        "getenv('TORCHSNAPSHOT_TPU_TRACE')",  # from os import getenv
    ],
)
def test_env_read_outside_knobs_flagged(expr):
    findings = _run(
        "knob-registry",
        f"""
        import os

        def f():
            return {expr}
        """,
    )
    assert len(findings) == 1


def test_env_read_inside_knobs_clean():
    findings = _run(
        "knob-registry",
        """
        import os

        def get_trace():
            return os.environ.get("TORCHSNAPSHOT_TPU_TRACE")
        """,
        filename="torchsnapshot_tpu/knobs.py",
    )
    assert findings == []


def test_tool_tsnp_env_read_clean():
    # TSNP_BENCH_* process controls in repo tooling are not library
    # knobs; only the package itself must route TSNP_* through knobs.py
    findings = _run(
        "knob-registry",
        """
        import os

        STATE = os.environ.get("TSNP_BENCH_STATE_DIR", ".")
        """,
        filename="tools/soak.py",
    )
    assert findings == []


def test_unrelated_env_read_clean():
    findings = _run(
        "knob-registry",
        """
        import os

        def f():
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        """,
    )
    assert findings == []


# ----------------------------------------------------- retry-discipline


def test_retry_sleep_loop_around_storage_op_flagged():
    findings = _run(
        "retry-discipline",
        """
        import time

        def pull(storage, path):
            while True:
                try:
                    return storage.sync_read(path)
                except OSError:
                    time.sleep(2)
        """,
    )
    assert len(findings) == 1
    assert "resilience.retry_call" in findings[0].message


def test_retry_async_sleep_loop_around_kv_op_flagged():
    findings = _run(
        "retry-discipline",
        """
        import asyncio

        async def wait_peer(coord, key):
            for _ in range(10):
                v = coord.kv_try_get(key)
                if v is not None:
                    return v
                await asyncio.sleep(0.5)
        """,
    )
    assert len(findings) == 1


def test_retry_sleep_loop_without_storage_op_clean():
    findings = _run(
        "retry-discipline",
        """
        import time

        def wait_flag(flags):
            while not flags.get("done"):
                time.sleep(0.1)
        """,
    )
    assert findings == []


def test_retry_storage_loop_without_sleep_clean():
    findings = _run(
        "retry-discipline",
        """
        def drain(storage, paths):
            for p in paths:
                storage.sync_delete(p)
        """,
    )
    assert findings == []


def test_retry_discipline_exempts_resilience_module_and_non_package():
    src = """
    import time

    def loop(storage, path):
        while True:
            try:
                return storage.sync_read(path)
            except OSError:
                time.sleep(1)
    """
    assert _run(
        "retry-discipline", src,
        filename="torchsnapshot_tpu/resilience/retry.py",
    ) == []
    assert _run(
        "retry-discipline", src, filename="tools/soak.py"
    ) == []
    assert len(_run("retry-discipline", src)) == 1  # package default


def test_retry_sleep_loop_around_part_write_flagged():
    """Part-level entry points (StripedWriteHandle.write_part, the raw
    multipart client verbs, pwrite) carry the same retry obligation as
    whole-object ops — striping must not open a policy bypass."""
    for op in (
        "handle.write_part(0, 0, buf)",
        "client.upload_part(Bucket=b, Key=k, PartNumber=1, UploadId=u, Body=buf)",
        "os.pwrite(fd, buf, off)",
        "client.abort_multipart_upload(Bucket=b, Key=k, UploadId=u)",
    ):
        findings = _run(
            "retry-discipline",
            f"""
            import os, time

            def pump(handle, client, fd, b, k, u, off, buf):
                while True:
                    try:
                        return {op}
                    except OSError:
                        time.sleep(1)
            """,
        )
        assert len(findings) == 1, op


def test_retry_part_write_without_sleep_clean():
    findings = _run(
        "retry-discipline",
        """
        async def drive(handle, spans):
            for i, (lo, hi) in enumerate(spans):
                await handle.write_part(i, lo, memoryview(b"x"))
        """,
    )
    assert findings == []


def test_retry_sleep_in_nested_def_not_attributed_to_loop():
    findings = _run(
        "retry-discipline",
        """
        import time

        def schedule(storage, paths):
            for p in paths:
                def backoff():
                    time.sleep(1)
                storage.sync_write(p)
        """,
    )
    assert findings == []


def test_retry_nested_qualifying_loops_report_innermost_only():
    findings = _run(
        "retry-discipline",
        """
        import time

        def pump(storage, batches):
            for batch in batches:
                while True:
                    try:
                        storage.sync_write(batch)
                        break
                    except OSError:
                        time.sleep(1)
        """,
    )
    assert len(findings) == 1
    assert findings[0].line == 6  # the while, not the for


# ------------------------------------------------------ instrumentation


def test_instrumentation_pass_flags_naked_public_method():
    findings = _run(
        "instrumentation",
        """
        class Snapshot:
            def restore(self, app_state):
                with log_event(Event("restore")):
                    return 1

            async def async_probe(self):
                async with thing:
                    with span("y"):
                        return 3

            def naked(self):
                return 2
        """,
        filename="torchsnapshot_tpu/snapshot.py",
    )
    assert len(findings) == 1
    assert "Snapshot.naked" in findings[0].message


def test_instrumentation_scoped_to_target_files():
    findings = _run(
        "instrumentation",
        """
        class Snapshot:
            def naked(self):
                return 2
        """,
        filename="torchsnapshot_tpu/other.py",
    )
    assert findings == []


def test_sibling_method_findings_have_distinct_fingerprints():
    # two unbracketed public methods of one class must not collapse to
    # one fingerprint, or the baseline ratchet couldn't tell "fixed A"
    # from "fixed A, regressed B"
    findings = _run(
        "instrumentation",
        """
        class Snapshot:
            def naked_a(self):
                return 1

            def naked_b(self):
                return 2
        """,
        filename="torchsnapshot_tpu/snapshot.py",
    )
    assert len(findings) == 2
    assert len({f.fingerprint for f in findings}) == 2
    assert {f.context for f in findings} == {
        "Snapshot.naked_a", "Snapshot.naked_b",
    }


def test_instrumentation_covers_stripe_entry_points():
    """The stripe engine's module-level entry points bypass the
    instrument_storage wrappers, so they are covered directly — an
    unbracketed striped_write must be flagged."""
    findings = _run(
        "instrumentation",
        """
        async def striped_write(storage, path, buf):
            handle = await storage.begin_striped_write(path, len(buf))
            await handle.complete()

        async def striped_read(storage, path, *, offset, length, into=None):
            with obs.span("stripe/read", path=path):
                return None
        """,
        filename="torchsnapshot_tpu/storage/stripe.py",
    )
    assert len(findings) == 1
    assert "striped_write" in findings[0].message


def test_check_source_without_module_functions_ignores_global_coverage():
    # the pre-migration API applied `module_functions or ()`: calling
    # check_source on a covered path WITHOUT module_functions must not
    # leak the global MODULE_FUNCTIONS entry into the check
    from tools.lint.passes import instrumentation as instr

    src = "def delete_snapshot(p):\n    return p\n"
    assert instr.check_source(src, {}, "torchsnapshot_tpu/manager.py") == []
    # and the real registry entry survives the temporary masking
    assert "delete_snapshot" in instr.MODULE_FUNCTIONS[
        "torchsnapshot_tpu/manager.py"
    ]


def test_check_instrumentation_shim_back_compat():
    """The deprecation shim keeps the original module API working."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_instrumentation_shim",
        os.path.join(_REPO_ROOT, "tools", "check_instrumentation.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check_repo(_REPO_ROOT) == []
    src = "class Snapshot:\n    def naked(self):\n        return 1\n"
    violations = mod.check_source(src, {"Snapshot": set()}, "x.py")
    assert len(violations) == 1 and "Snapshot.naked" in violations[0]


# --------------------------------------------- allowlist + baseline law


def test_allowlist_requires_written_justification():
    with pytest.raises(LintConfigError):
        validate_allowlist(
            [
                Allow(
                    pass_id="exception-hygiene",
                    file="x.py",
                    context="f",
                    justification="ok",  # token-length: rejected
                )
            ]
        )
    validate_allowlist(list(ALLOWLIST))  # the shipped entries comply


def test_allowlist_suppresses_only_matching_context(tmp_path):
    pkg = tmp_path / "torchsnapshot_tpu"
    pkg.mkdir()
    (pkg / "x.py").write_text(
        textwrap.dedent(
            """
            def allowed():
                try:
                    work()
                except Exception:
                    pass

            def not_allowed():
                try:
                    work()
                except Exception:
                    pass
            """
        )
    )
    allow = Allow(
        pass_id="exception-hygiene",
        file="torchsnapshot_tpu/x.py",
        context="allowed",
        justification=(
            "fixture: this swallow is the documented contract of "
            "allowed(), reviewed here"
        ),
    )
    result = run_repo(str(tmp_path), ALL_PASSES, allowlist=[allow])
    assert len(result.allowlisted) == 1
    assert len(result.unbaselined) == 1
    assert result.unbaselined[0].context == "not_allowed"


def test_baseline_tolerates_then_ratchets(tmp_path):
    pkg = tmp_path / "torchsnapshot_tpu"
    pkg.mkdir()
    violating = textwrap.dedent(
        """
        def legacy():
            try:
                work()
            except Exception:
                pass
        """
    )
    (pkg / "x.py").write_text(violating)
    # 1) baseline the legacy finding → run is clean
    first = run_repo(str(tmp_path), ALL_PASSES)
    assert len(first.unbaselined) == 1
    bl_path = tmp_path / "baseline.json"
    save_baseline(str(bl_path), first.unbaselined)
    baseline = load_baseline(str(bl_path))
    second = run_repo(str(tmp_path), ALL_PASSES, baseline=baseline)
    assert second.ok and len(second.baselined) == 1
    # 2) a NEW finding (same file, new context) is NOT covered
    (pkg / "x.py").write_text(
        violating + textwrap.dedent(
            """
            def fresh():
                try:
                    work()
                except Exception:
                    pass
            """
        )
    )
    third = run_repo(str(tmp_path), ALL_PASSES, baseline=baseline)
    assert not third.ok
    assert [f.context for f in third.unbaselined] == ["fresh"]
    # 3) the ratchet refuses growth, permits shrink-to-empty
    assert check_ratchet(baseline, third.baselined + third.unbaselined)
    assert check_ratchet(baseline, []) == []


def test_update_baseline_conflicts_with_no_baseline(capsys):
    assert main(["--update-baseline", "--no-baseline"]) == 2
    assert "conflict" in capsys.readouterr().err


def test_malformed_baseline_is_config_error(tmp_path):
    # hand-edited/merge-damaged baseline values must hit the exit-2
    # LintConfigError contract, not an interpreter traceback
    bad = tmp_path / "baseline.json"
    bad.write_text('{"findings": {"a:b:c": "three"}}')
    with pytest.raises(LintConfigError):
        load_baseline(str(bad))
    assert main(["--baseline", str(bad)]) == 2


def test_update_baseline_refuses_partial_scope(tmp_path, capsys):
    # a pass-subset (or foreign-root) rewrite would erase every other
    # pass's baselined fingerprints — must be refused, not honored
    assert main(["--pass", "exception-hygiene", "--update-baseline"]) == 2
    assert "full run" in capsys.readouterr().err
    assert main([str(tmp_path), "--update-baseline"]) == 2
    assert "refusing" in capsys.readouterr().err
    assert load_baseline(DEFAULT_BASELINE) == {}  # untouched
    # a RELATIVE spelling of the repo root is still the same checkout —
    # the guard normalizes paths instead of comparing raw strings
    cwd = os.getcwd()
    os.chdir(_REPO_ROOT)
    try:
        assert main([".", "--update-baseline"]) == 0
    finally:
        os.chdir(cwd)
    assert load_baseline(DEFAULT_BASELINE) == {}  # clean repo: no-op


def test_changed_mode_clean_and_guards(capsys, tmp_path):
    """--changed is the pre-commit invocation: per-file passes report
    only on files changed vs the ref, the interprocedural passes
    still run package-wide, and partial-scope guards hold (no
    baseline rewrite, no staleness reporting)."""
    # this checkout is a git repo and currently clean under the gate
    assert main(["--changed"]) == 0
    captured = capsys.readouterr()
    assert "stale" not in captured.err  # partial scope: no staleness
    assert main(["--changed", "HEAD", "--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["ok"] is True and data["unused_allows"] == []
    # a changed-subset baseline rewrite would erase the full scope
    assert main(["--changed", "--update-baseline"]) == 2
    assert "conflict" in capsys.readouterr().err
    # a non-checkout root falls back to the full scan with a warning
    pkg = tmp_path / "torchsnapshot_tpu"
    pkg.mkdir()
    (pkg / "x.py").write_text("def f(coord):\n    coord.kv_set('d', '1')\n")
    assert main([str(tmp_path), "--changed"]) == 1
    captured = capsys.readouterr()
    assert "full scan" in captured.err
    assert "kv-hygiene" in captured.out


def test_changed_files_rebases_subtree_paths(tmp_path):
    """Regression (review finding): `git diff --name-only` emits
    toplevel-relative paths; when the scan root is a SUBDIRECTORY of
    the checkout (vendored tree), they must be re-based to the root or
    --changed silently lints nothing."""
    import subprocess

    from tools.lint.cli import changed_files

    def git(*args):
        subprocess.run(
            ["git", "-C", str(tmp_path), *args],
            check=True, capture_output=True,
            env={
                **os.environ,
                "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t",
            },
        )

    sub = tmp_path / "vendored" / "torchsnapshot_tpu"
    sub.mkdir(parents=True)
    (sub / "x.py").write_text("def f():\n    pass\n")
    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    (sub / "x.py").write_text("def f():\n    return 1\n")
    (sub / "new.py").write_text("def g():\n    pass\n")
    # scan root = the vendored subtree: paths must come back relative
    # to it, tracked-changed and untracked alike
    got = changed_files(str(tmp_path / "vendored"), "HEAD")
    assert got == {
        "torchsnapshot_tpu/x.py", "torchsnapshot_tpu/new.py",
    }
    # scan root = the toplevel: unchanged behavior
    got = changed_files(str(tmp_path), "HEAD")
    assert got == {
        "vendored/torchsnapshot_tpu/x.py",
        "vendored/torchsnapshot_tpu/new.py",
    }


def test_pass_subset_does_not_report_skipped_passes_allows_stale(capsys):
    # exception-hygiene allowlist entries can't match a knob-registry
    # subset run; reporting them stale would invite deleting entries
    # the full run still needs
    assert main(["--pass", "knob-registry"]) == 0
    captured = capsys.readouterr()
    assert "stale" not in captured.err
    assert main(["--pass", "knob-registry", "--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["unused_allows"] == []


def test_json_output_reports_stale_allows(capsys, monkeypatch):
    import tools.lint.cli as cli_mod

    stale = Allow(
        pass_id="exception-hygiene",
        file="nonexistent.py",
        context="ghost",
        justification=(
            "fixture: deliberately matches nothing so the staleness "
            "report path is exercised"
        ),
    )
    monkeypatch.setattr(
        cli_mod, "ALLOWLIST", tuple(ALLOWLIST) + (stale,)
    )
    assert main(["--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert "exception-hygiene:nonexistent.py:ghost" in data["unused_allows"]


def test_shipped_baseline_is_empty():
    """The repo starts clean: every real finding this PR surfaced was
    fixed or allowlisted — the ratchet exists for future legacy debt,
    and an empty baseline means none was grandfathered in."""
    assert load_baseline(DEFAULT_BASELINE) == {}


def test_instrumentation_covers_codec_entry_points():
    """The codec layer's pipeline entry points must carry spans — an
    unbracketed encode_frame_async would make compression latency
    invisible exactly where a slow take needs attribution."""
    findings = _run(
        "instrumentation",
        """
        async def encode_frame_async(view, spec, stride, executor):
            return encode_frame(view, spec, stride)

        async def framed_read(storage, path, table):
            with obs.span("codec/framed_read", path=path):
                return None
        """,
        filename="torchsnapshot_tpu/codec.py",
    )
    assert len(findings) == 1
    assert "encode_frame_async" in findings[0].message


def test_instrumentation_covers_fastio_entry_points():
    """The fast-I/O engine's byte-moving methods must carry spans —
    once the engine is on, fs I/O time lives inside them, and an
    unbracketed engine would make the fastest path the least
    attributable one."""
    from tools.lint.passes.instrumentation import TARGETS

    cov = TARGETS["torchsnapshot_tpu/storage/fastio.py"]
    assert "FastIOEngine" in cov
    # the byte movers are ENFORCED, not allowlisted away
    assert not {"write_file", "read_into", "pwrite_part"} & cov["FastIOEngine"]
    findings = _run(
        "instrumentation",
        """
        class FastIOEngine:
            def write_file(self, path, buf, sync_file, want_digest):
                return None

            def read_into(self, path, offset, length, out):
                with obs.span("fastio/read_into", path=path):
                    return 0
        """,
        filename="torchsnapshot_tpu/storage/fastio.py",
    )
    assert len(findings) == 1
    assert "write_file" in findings[0].message


def test_instrumentation_covers_serving_read_entry_points():
    """Serving read path pins: the zero-copy mapping call (fs.mmap_read)
    and the shared-host cache's single-flight fill must stay
    span-covered — the fill holds a cross-process lock around a durable
    GET, and the mapping is where serving I/O time would otherwise
    vanish from copy-based accounting."""
    from tools.lint.passes import instrumentation as instr

    assert "mmap_read" in instr.MODULE_FUNCTIONS[
        "torchsnapshot_tpu/storage/fs.py"
    ]
    assert "singleflight_fill" in instr.MODULE_FUNCTIONS[
        "torchsnapshot_tpu/storage/hostcache.py"
    ]
    findings = _run(
        "instrumentation",
        """
        async def singleflight_fill(plugin, path, cfile):
            lock_fd = _lock_acquire(plugin._lock_path(cfile))
            return None
        """,
        filename="torchsnapshot_tpu/storage/hostcache.py",
    )
    assert len(findings) == 1
    assert "singleflight_fill" in findings[0].message
    findings = _run(
        "instrumentation",
        """
        def mmap_read(full, byte_range, path=""):
            return None
        """,
        filename="torchsnapshot_tpu/storage/fs.py",
    )
    assert len(findings) == 1
    assert "mmap_read" in findings[0].message


def test_instrumentation_serving_clean_when_bracketed():
    findings = _run(
        "instrumentation",
        """
        def mmap_read(full, byte_range, path=""):
            with obs.span("storage/mmap_read", path=path):
                return None
        """,
        filename="torchsnapshot_tpu/storage/fs.py",
    )
    assert findings == []


def test_instrumentation_codec_clean_when_bracketed():
    findings = _run(
        "instrumentation",
        """
        async def encode_frame_async(view, spec, stride, executor):
            with obs.span("codec/encode_part"):
                return encode_frame(view, spec, stride)

        async def framed_read(storage, path, table):
            with obs.span("codec/framed_read", path=path):
                return None

        def encode_frame(view, spec, stride):
            return b""  # deliberately uncovered (hot sync path)
        """,
        filename="torchsnapshot_tpu/codec.py",
    )
    assert findings == []


@pytest.mark.parametrize(
    "expr",
    [
        "os.environ.get('TORCHSNAPSHOT_TPU_CODEC')",
        "os.environ['TORCHSNAPSHOT_TPU_CODEC_LEVEL']",
        "os.getenv('TORCHSNAPSHOT_TPU_CODEC_MIN_RATIO', '1.05')",
    ],
)
def test_codec_knob_env_reads_flagged_outside_knobs(expr):
    """The three codec knobs are registry knobs like any other: raw env
    reads outside knobs.py bypass override helpers and defaults."""
    findings = _run(
        "knob-registry",
        f"""
        import os

        def f():
            return {expr}
        """,
        filename="torchsnapshot_tpu/codec.py",
    )
    assert len(findings) == 1


def test_codec_knob_reads_via_knobs_module_clean():
    findings = _run(
        "knob-registry",
        """
        from . import knobs

        def resolve():
            return (
                knobs.get_codec(),
                knobs.get_codec_level(),
                knobs.get_codec_min_ratio(),
            )
        """,
        filename="torchsnapshot_tpu/codec.py",
    )
    assert findings == []


def test_instrumentation_covers_obs_aggregate_goodput_and_promoter():
    """The fleet-observability entry points are pinned into the
    instrumentation pass's coverage map: dropping them in a refactor
    must fail here, not silently shrink trace completeness."""
    from tools.lint.passes.instrumentation import MODULE_FUNCTIONS, TARGETS

    assert {
        "publish", "exchange_and_merge", "write_obsrecord",
        "read_obsrecord",
    } <= MODULE_FUNCTIONS["torchsnapshot_tpu/obs/aggregate.py"]
    assert {
        "take_begin", "take_unblocked", "durable_commit",
    } <= MODULE_FUNCTIONS["torchsnapshot_tpu/obs/goodput.py"]
    # Promoter public methods are checked (pause/resume allowlisted as
    # test-only event flips)
    assert TARGETS["torchsnapshot_tpu/tier/promoter.py"]["Promoter"] == {
        "pause", "resume",
    }


def test_instrumentation_covers_cas_entry_points():
    """The chunk store's engines, the index rebuild, and the GC/commit
    mutations (cas/) are pinned into the instrumentation coverage map —
    the skip-vs-write decision and chunk deletions are exactly what an
    incremental-checkpoint incident review reconstructs."""
    from tools.lint.passes.instrumentation import MODULE_FUNCTIONS

    assert {
        "chunked_write", "cas_streamed_write", "chunked_read",
    } <= MODULE_FUNCTIONS["torchsnapshot_tpu/cas/store.py"]
    assert {"fsck"} <= MODULE_FUNCTIONS["torchsnapshot_tpu/cas/index.py"]
    assert {
        "commit_refs", "release_step", "run_gc",
    } <= MODULE_FUNCTIONS["torchsnapshot_tpu/cas/gc.py"]


def test_instrumentation_covers_topology_entry_points():
    """The multislice subsystem's entry points (topology/) are pinned
    into the instrumentation coverage map: the placement exchange and
    the fan-out publish/fetch transport can each stall a whole slice's
    restore, so dropping their spans in a refactor must fail here."""
    from tools.lint.passes.instrumentation import MODULE_FUNCTIONS

    assert {"detect_topology"} <= MODULE_FUNCTIONS[
        "torchsnapshot_tpu/topology/model.py"
    ]
    assert {"publish_object", "fetch_published"} <= MODULE_FUNCTIONS[
        "torchsnapshot_tpu/topology/fanout.py"
    ]


def test_instrumentation_covers_transport_entry_points():
    """The payload-transport subsystem (transport/) is pinned into the
    instrumentation coverage map: engine selection decides where every
    redistribution byte travels, and the byte movers of BOTH engines
    (plus the session consume wait) must stay span-covered — the
    fastest path must never become the least attributable one."""
    from tools.lint.passes.instrumentation import MODULE_FUNCTIONS, TARGETS

    assert {"resolve_transport"} <= MODULE_FUNCTIONS[
        "torchsnapshot_tpu/transport/__init__.py"
    ]
    kv_allow = TARGETS["torchsnapshot_tpu/transport/kv.py"]["KVTransport"]
    assert not {"publish", "try_fetch"} & kv_allow
    coll = TARGETS["torchsnapshot_tpu/transport/collective.py"]
    assert not {"publish", "try_fetch", "device_move"} & coll[
        "CollectiveTransport"
    ]
    assert "consume" not in coll["CollectiveFanoutSession"]


def test_instrumentation_covers_continuous_entry_points():
    """The continuous checkpoint loop's transitions (step / drain /
    close / promote / restore_latest via the class check), the recovery
    entry point (the measured RTO), the store's verified chunk fan-in,
    and the SIGTERM drain are pinned into the instrumentation coverage
    map — a preemption incident review reconstructs exactly these."""
    from tools.lint.passes.instrumentation import MODULE_FUNCTIONS, TARGETS

    cc_allow = TARGETS["torchsnapshot_tpu/continuous/loop.py"][
        "ContinuousCheckpointer"
    ]
    # the loss-bounding transitions must NOT be allowlisted away
    assert not {
        "step", "drain", "close", "promote", "restore_latest"
    } & cc_allow
    assert {"read_state", "read_chunks"} & set(
        TARGETS["torchsnapshot_tpu/continuous/store.py"][
            "ContinuousStore"
        ]
    ) == set()
    assert {"recover_state"} <= MODULE_FUNCTIONS[
        "torchsnapshot_tpu/continuous/recover.py"
    ]
    assert {"notify_preemption"} <= MODULE_FUNCTIONS[
        "torchsnapshot_tpu/resilience/preemption.py"
    ]


def test_collective_safety_designated_reader_kv_pattern_clean():
    """The fan-out restore's designated-reader protocol is rank-
    conditional BY DESIGN — the publisher kv_sets, siblings kv_get —
    and explicit-key KV ops are the sanctioned asymmetric pattern.
    The collective-safety pass must accept exactly that shape."""
    findings = _run(
        "collective-safety",
        """
        def fan_read(coord, topo, path, inner_read, fetch):
            if topo.designated_reader(path) == coord.rank:
                inner_read(path)
                coord.kv_publish_blob("fan/p", b"bytes")
            else:
                data = coord.kv_try_get("fan/p/meta")
            coord.barrier()  # symmetric epilogue stays legal
        """,
    )
    assert findings == []


def test_collective_safety_transport_gate_protocol_clean():
    """The collective transport's two-gate session protocol: the
    source rank kv_sets go/go2 gates while consumers kv_get and ack —
    explicit-key KV control traffic under rank conditionals (the
    sanctioned asymmetric pattern) — and the broadcast itself sits in
    the symmetric epilogue every process reaches.  The pass must
    accept exactly that shape: payload collectives lockstep, control
    plane asymmetric."""
    findings = _run(
        "collective-safety",
        """
        def session_transfer(coord, source_rank, parts):
            if coord.rank == source_rank:
                coord.kv_set("uid/x/0/go", "ok:1:1:128:0:1")
                coord.kv_get("uid/x/0/ack/1")
                coord.kv_set("uid/x/0/go2", "go")
            else:
                coord.kv_get("uid/x/0/go")
                coord.kv_set("uid/x/0/ack/1", "1")
                coord.kv_get("uid/x/0/go2")
            for part in parts:  # every process enters every broadcast
                coord.broadcast_object(part)
        """,
    )
    assert findings == []


def test_collective_safety_flags_source_only_broadcast():
    """...but a broadcast entered only under the source branch is the
    SPMD wedge the session protocol exists to prevent — consumers
    never arrive and the source blocks forever."""
    findings = _run(
        "collective-safety",
        """
        def session_transfer(coord, source_rank, part):
            if coord.rank == source_rank:
                coord.broadcast_object(part)
            else:
                coord.kv_get("uid/x/0/go")
        """,
    )
    assert len(findings) == 1
    assert "broadcast_object" in findings[0].message


def test_collective_safety_flags_collective_in_designated_branch():
    """...but an actual COLLECTIVE under the designated-reader branch
    is the SPMD deadlock the pass exists for: only the designated rank
    would arrive."""
    findings = _run(
        "collective-safety",
        """
        def fan_read(coord, topo, path):
            if topo.designated_reader(path) == coord.rank:
                coord.kv_exchange("fan/p", "v")
            else:
                coord.barrier()
        """,
    )
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "kv_exchange" in messages and "barrier" in messages


def test_instrumentation_flags_uncovered_goodput_entry_point():
    from tools.lint.passes.instrumentation import check_source

    bare = "def take_begin(path):\n    return 0\n"
    violations = check_source(
        bare, {}, "torchsnapshot_tpu/obs/goodput.py",
        module_functions={"take_begin"},
    )
    assert len(violations) == 1 and "take_begin" in violations[0]
    bracketed = (
        "def take_begin(path):\n"
        "    with obs.span('goodput/take_begin'):\n"
        "        return 0\n"
    )
    assert check_source(
        bracketed, {}, "torchsnapshot_tpu/obs/goodput.py",
        module_functions={"take_begin"},
    ) == []


# ------------------------------------------------------- async-blocking


def test_async_blocking_open_flagged():
    findings = _run(
        "async-blocking",
        """
        async def fill(path):
            with open(path, "wb") as f:
                f.write(b"x")
        """,
    )
    assert len(findings) == 1
    assert "open" in findings[0].message


def test_async_blocking_time_sleep_and_from_import_flagged():
    findings = _run(
        "async-blocking",
        """
        import time
        from time import sleep

        async def a():
            time.sleep(1)

        async def b():
            sleep(1)
        """,
    )
    assert len(findings) == 2


def test_async_blocking_asyncio_and_aiofiles_clean():
    findings = _run(
        "async-blocking",
        """
        import asyncio

        async def f(path):
            await asyncio.sleep(0.1)
            async with aiofiles.open(path, "rb") as f:
                return await f.read()
        """,
    )
    assert findings == []


def test_async_blocking_sync_kv_wait_flagged():
    findings = _run(
        "async-blocking",
        """
        async def wait_peers(coord, uid):
            coord.kv_get(f"{uid}/depart")
            coord.barrier()
        """,
    )
    assert len(findings) == 2


def test_async_blocking_executor_dispatch_clean():
    # the callable is passed as a REFERENCE — structurally exempt, no
    # suppression comment needed
    findings = _run(
        "async-blocking",
        """
        async def wait_peers(coord, uid, loop):
            await loop.run_in_executor(None, coord.kv_get, f"{uid}/depart")
            await asyncio.to_thread(coord.barrier)
        """,
    )
    assert findings == []


def test_async_blocking_result_and_thread_join_flagged():
    findings = _run(
        "async-blocking",
        """
        async def f(fut, thread):
            x = fut.result()
            thread.join(5.0)
            return x
        """,
    )
    assert len(findings) == 2


def test_async_blocking_str_and_path_join_clean():
    findings = _run(
        "async-blocking",
        """
        async def f(parts, base, os):
            a = ",".join(parts)
            b = os.path.join(base, "x")
            return a + b
        """,
    )
    assert findings == []


def test_async_blocking_flock_and_subprocess_flagged():
    findings = _run(
        "async-blocking",
        """
        import fcntl, subprocess

        async def f(fd):
            fcntl.flock(fd, fcntl.LOCK_EX)
            subprocess.check_output(["ls"])
        """,
    )
    assert len(findings) == 2


def test_async_blocking_indirect_helper_chain_flagged():
    """A blocking call hidden one hop away in a module-local sync
    helper is reachable from the event loop all the same — the call
    graph (FileUnit.callers/local_defs) carries the check through."""
    findings = _run(
        "async-blocking",
        """
        import time

        def backoff():
            time.sleep(1)

        def helper():
            backoff()

        async def drive():
            helper()
        """,
    )
    assert len(findings) == 1
    assert "helper" in findings[0].message
    assert findings[0].context == "drive"


def test_async_blocking_nested_def_and_sync_fn_clean():
    # a nested def's body runs when called (possibly on an executor);
    # blocking calls in plain sync functions are their callers' concern
    findings = _run(
        "async-blocking",
        """
        async def f(loop, path):
            def work():
                with open(path) as fh:
                    return fh.read()
            return await loop.run_in_executor(None, work)

        def sync_helper(path):
            return open(path).read()
        """,
    )
    assert findings == []


# ------------------------------------------------------ resource-pairing


def test_resource_pairing_gate_leak_flagged():
    findings = _run(
        "resource-pairing",
        """
        async def one(gate, span):
            await gate.acquire(span)
            piece = stage(span)
            write(piece)
            gate.release(span)
        """,
    )
    assert len(findings) == 1
    assert "byte-gate" in findings[0].message


def test_resource_pairing_gate_finally_clean():
    findings = _run(
        "resource-pairing",
        """
        async def one(gate, span):
            await gate.acquire(span)
            try:
                piece = stage(span)
                write(piece)
            finally:
                gate.release(span)
        """,
    )
    assert findings == []


def test_resource_pairing_with_item_sanctioned():
    findings = _run(
        "resource-pairing",
        """
        async def one(window, span):
            async with window.acquire(span):
                write(stage(span))
        """,
    )
    assert findings == []


def test_resource_pairing_partial_release_still_needs_total():
    # an early partial release on one branch does not discharge the
    # obligation — only the finally does
    findings = _run(
        "resource-pairing",
        """
        async def one(gate, held):
            await gate.acquire(held)
            frame = encode()
            early = held - len(frame)
            if early:
                gate.release(early)
            write(frame)
            gate.release(held)
        """,
    )
    assert len(findings) == 1


def test_resource_pairing_budget_debit_credit():
    flagged = _run(
        "resource-pairing",
        """
        def admit(budget, p):
            budget.debit(p.cost)
            launch(p)
        """,
    )
    assert len(flagged) == 1 and "budget" in flagged[0].message
    clean = _run(
        "resource-pairing",
        """
        def admit(budget, p):
            budget.debit(p.cost)
            try:
                launch(p)
            except BaseException:
                budget.credit(p.cost)
                raise
            budget.credit(p.cost)
        """,
    )
    assert clean == []


def test_resource_pairing_breaker_probe():
    """The tier plugin's shape: allow() in the if-test claims the probe
    slot on the TRUE branch only; every route out of it must record an
    outcome (the false branch owes nothing)."""
    clean = _run(
        "resource-pairing",
        """
        async def read(self, io):
            if self._breaker.allow():
                try:
                    await self._fast_read(io)
                    self._breaker.record_success()
                    return
                except OSError:
                    self._breaker.record_failure()
                except BaseException:
                    self._breaker.release_probe()
                    raise
            await self._fallback(io)
        """,
    )
    assert clean == []
    flagged = _run(
        "resource-pairing",
        """
        async def read(self, io):
            if self._breaker.allow():
                await self._fast_read(io)
                self._breaker.record_success()
                return
            await self._fallback(io)
        """,
    )
    # _fast_read can raise past record_success: probe slot wedges
    assert len(flagged) == 1 and "breaker" in flagged[0].message


def test_resource_pairing_striped_handle():
    flagged = _run(
        "resource-pairing",
        """
        async def put(storage, path, view):
            handle = await storage.begin_striped_write(path, len(view))
            await handle.write_part(0, 0, view)
            await handle.complete()
        """,
    )
    assert len(flagged) == 1
    assert "striped-handle" in flagged[0].message
    clean = _run(
        "resource-pairing",
        """
        async def put(storage, path, view):
            handle = await storage.begin_striped_write(path, len(view))
            try:
                await handle.write_part(0, 0, view)
            except BaseException:
                await handle.abort()
                raise
            await handle.complete()
        """,
    )
    assert clean == []


def test_resource_pairing_handle_handoff_counts_as_release():
    # handing the handle to a helper (the _abort_quiet shape) moves
    # ownership; returning it does too
    findings = _run(
        "resource-pairing",
        """
        async def put(storage, path, view):
            handle = await storage.begin_striped_write(path, len(view))
            try:
                await handle.write_part(0, 0, view)
            except BaseException:
                await shielded_abort(handle)
                raise
            await handle.complete()

        async def open_only(storage, path, size):
            handle = await storage.begin_striped_write(path, size)
            return handle
        """,
    )
    assert findings == []


def test_resource_pairing_lock_receivers_left_to_lock_discipline():
    findings = _run(
        "resource-pairing",
        """
        def f(self):
            self._lock.acquire()
            work()
        """,
    )
    assert findings == []  # lock-discipline owns this shape


# ---------------------------------------------------------- kv-hygiene


def test_kv_hygiene_literal_key_flagged():
    findings = _run(
        "kv-hygiene",
        """
        def commit(coord):
            coord.kv_set("done", "1")
        """,
    )
    assert len(findings) == 1
    assert "namespaced" in findings[0].message


def test_kv_hygiene_literal_headed_fstring_flagged():
    findings = _run(
        "kv-hygiene",
        """
        def publish(coord, rank):
            coord.kv_set(f"fan/{rank}", "payload")
        """,
    )
    assert len(findings) == 1


def test_kv_hygiene_uid_headed_keys_clean():
    findings = _run(
        "kv-hygiene",
        """
        def commit(coord, uid, rank):
            coord.kv_set(f"{uid}/arrive/{rank}", "ok")
            coord.kv_set(key_helper(uid, rank), "ok")
        """,
    )
    assert findings == []


def test_kv_hygiene_publish_without_delete_flagged():
    findings = _run(
        "kv-hygiene",
        """
        def publish(coord, prefix, buf):
            coord.kv_publish_blob(f"{prefix}/blob", buf)
        """,
    )
    assert len(findings) == 1
    assert "kv_try_delete" in findings[0].message


def test_kv_hygiene_publish_with_module_delete_clean():
    findings = _run(
        "kv-hygiene",
        """
        def publish(coord, prefix, buf):
            coord.kv_publish_blob(f"{prefix}/blob", buf)

        def cleanup(coord, prefix, nparts):
            coord.kv_try_delete(f"{prefix}/meta")
            for i in range(nparts):
                coord.kv_try_delete(f"{prefix}/p{i}")
        """,
    )
    assert findings == []


def test_kv_hygiene_heartbeat_without_delete_flagged():
    """Liveness keys (the /hb/ segment — continuous/heartbeat.py's
    convention) are publish-paired-with-delete like fan-out blobs: a
    stale heartbeat reads as a live-but-stalled rank forever."""
    findings = _run(
        "kv-hygiene",
        """
        def beat(coord, ns, rank, step):
            coord.kv_set(f"{ns}/hb/{rank}", str(step))
        """,
    )
    assert len(findings) == 1
    assert "heartbeat" in findings[0].message
    assert "kv_try_delete" in findings[0].message


def test_kv_hygiene_heartbeat_with_module_delete_clean():
    findings = _run(
        "kv-hygiene",
        """
        def beat(coord, ns, rank, step):
            coord.kv_set(f"{ns}/hb/{rank}", str(step))

        def clear(coord, ns, rank):
            coord.kv_try_delete(f"{ns}/hb/{rank}")
        """,
    )
    assert findings == []


def test_kv_hygiene_plain_uid_kv_set_needs_no_delete():
    """Only heartbeat-segment keys trigger the pairing rule — ordinary
    uid-namespaced control keys (done-keys, arrive-keys) are consumed
    by waiters and stay exempt."""
    findings = _run(
        "kv-hygiene",
        """
        def done(coord, uid, rank):
            coord.kv_set(f"{uid}/tierdone/{rank}", "ok")
        """,
    )
    assert findings == []


def test_kv_hygiene_liveness_session_shape_clean():
    """The liveness publisher's exact shape (resilience/liveness.py): a
    self-attribute-namespaced heartbeat stamp paired with the session's
    own ``stop()`` delete in the same module is sanctioned — the stamp
    key never outlives a clean exit."""
    findings = _run(
        "kv-hygiene",
        """
        class Session:
            def _publish_loop(self, coord, seq):
                coord.kv_set(f"{self._ns}/hb/{coord.rank}", str(seq))

            def stop(self, coord):
                coord.kv_try_delete(f"{self._ns}/hb/{coord.rank}")
        """,
    )
    assert findings == []


def test_kv_hygiene_takeover_recovery_keys_exempt():
    """The commit-recovery protocol's control keys (takeover plans,
    CRC re-exchange, commit acks) are uid-namespaced one-shot keys
    consumed by waiters — no delete pairing required."""
    findings = _run(
        "kv-hygiene",
        """
        def recover(coord, uid, rank, plan, crcs):
            coord.kv_set(f"{uid}/takeover/plan/{rank}", plan)
            coord.kv_set(f"{uid}/takeover/crcs/{rank}", crcs)
            coord.kv_set(f"{uid}/takeover/commit/{rank}", "ok")
        """,
    )
    assert findings == []


def test_kv_hygiene_scoped_to_package():
    findings = _run(
        "kv-hygiene",
        """
        def commit(coord):
            coord.kv_set("done", "1")
        """,
        filename="tools/soak.py",
    )
    assert findings == []


# ------------------------------------------------------ metric-registry


def test_metric_registry_unknown_instrument_flagged():
    findings = _run(
        "metric-registry",
        """
        def f(obs):
            obs.counter("tier.bogus_metric").inc()
        """,
    )
    assert len(findings) == 1
    assert "gen_metric_registry" in findings[0].message


def test_metric_registry_known_names_and_families_clean():
    findings = _run(
        "metric-registry",
        """
        def f(obs, backend):
            obs.counter("tier.fast_hits").inc()
            obs.histogram(f"storage.{backend}.write_latency_s").observe(1)
            obs.gauge("goodput.overhead_fraction").set(0.1)
        """,
    )
    assert findings == []


def test_metric_registry_unknown_dynamic_family_flagged():
    findings = _run(
        "metric-registry",
        """
        def f(obs, backend):
            obs.counter(f"storage.{backend}.novel_thing").inc()
        """,
    )
    assert len(findings) == 1
    assert "DYNAMIC_FAMILIES" in findings[0].message


def test_metric_registry_reference_drift_flagged():
    # the doctor-CLI shape: reading a rollup by a name no instrument
    # registers reads 0 forever
    findings = _run(
        "metric-registry",
        """
        def rollup(counters):
            return counters.get("tier.fast_hitz", 0)
        """,
    )
    assert len(findings) == 1
    assert "tier.fast_hitz" in findings[0].message


def test_metric_registry_failpoint_sites_excluded():
    # failpoint SITE names share the dotted namespace by design
    findings = _run(
        "metric-registry",
        """
        def promote(group):
            failpoint("tier.promote.data", durable=group.url)
            obs.swallowed_exception("tier.plugin_close", None)
        """,
    )
    assert findings == []


def test_metric_registry_failpoint_site_kwarg_excluded():
    """A site literal handed through a ``failpoint_site=`` parameter
    (the budgeted-write engine's pass-through, used by the continuous
    loop) is a failpoint name, not a metric reference."""
    findings = _run(
        "metric-registry",
        """
        def replicate(items, storage, writer):
            writer(items, storage, failpoint_site="continuous.replicate")
        """,
    )
    assert findings == []
    # ...but the same literal in a non-failpoint keyword still drifts
    findings = _run(
        "metric-registry",
        """
        def replicate(items, storage, writer):
            writer(items, storage, label="continuous.bogus_name")
        """,
    )
    assert len(findings) == 1


def test_metric_registry_staleness_detected():
    findings = _run(
        "metric-registry",
        """
        NEW_METRIC = "tier.not_yet_registered"
        """,
        filename="torchsnapshot_tpu/obs/metrics.py",
    )
    msgs = " ".join(f.message for f in findings)
    assert "tier.not_yet_registered" in msgs  # missing from registry
    assert "no longer defined" in msgs  # registry names absent here


def test_metric_registry_generated_file_in_sync():
    """Regeneration must be a no-op: the committed registry matches
    what gen_metric_registry derives from obs/metrics.py right now."""
    from tools.lint.gen_metric_registry import derive_names
    from tools.lint.metric_registry_data import KNOWN_METRIC_NAMES

    assert derive_names(_REPO_ROOT) == set(KNOWN_METRIC_NAMES)


def test_metric_registry_real_metrics_source_clean():
    with open(
        os.path.join(_REPO_ROOT, "torchsnapshot_tpu", "obs", "metrics.py"),
        encoding="utf-8",
    ) as f:
        src = f.read()
    findings = run_source(
        src, "torchsnapshot_tpu/obs/metrics.py",
        [_BY_ID["metric-registry"]],
    )
    assert findings == []


# ------------------------------------ satellites: strengthened passes


def test_exception_hygiene_tuple_handler_flagged():
    findings = _run(
        "exception-hygiene",
        """
        def f():
            try:
                work()
            except (Exception, OSError):
                pass
        """,
    )
    assert len(findings) == 1


def test_exception_hygiene_bound_but_ignored_flagged():
    findings = _run(
        "exception-hygiene",
        """
        def f(self):
            try:
                work()
            except Exception as e:
                self.status = "failed"
        """,
    )
    assert len(findings) == 1
    assert "neither uses nor re-raises" in findings[0].message


def test_exception_hygiene_bound_and_used_clean():
    findings = _run(
        "exception-hygiene",
        """
        def f(self):
            try:
                work()
            except Exception as e:
                self.status = f"failed: {e}"
        """,
    )
    assert findings == []


def test_knob_registry_membership_read_flagged():
    findings = _run(
        "knob-registry",
        """
        import os

        def f():
            return "TORCHSNAPSHOT_TPU_TRACE" in os.environ

        def g():
            if "TSNP_S3_ENDPOINT_URL" not in os.environ:
                return None
        """,
    )
    assert len(findings) == 2


def test_knob_registry_unrelated_membership_clean():
    findings = _run(
        "knob-registry",
        """
        import os

        def f():
            return "JAX_PLATFORMS" in os.environ
        """,
    )
    assert findings == []


# ------------------------------------------- driver + CLI satellites


def test_syntax_error_becomes_driver_parse_error_finding(tmp_path):
    """A broken file must surface as one actionable finding, not kill
    the run: the rest of the tree still gets linted."""
    pkg = tmp_path / "torchsnapshot_tpu"
    pkg.mkdir()
    (pkg / "broken.py").write_text("def f(:\n")
    (pkg / "ok.py").write_text(
        "def g():\n    try:\n        w()\n    except Exception:\n"
        "        pass\n"
    )
    result = run_repo(str(tmp_path), ALL_PASSES)
    by_pass = {}
    for f in result.unbaselined:
        by_pass.setdefault(f.pass_id, []).append(f)
    assert len(by_pass["driver-parse-error"]) == 1
    assert by_pass["driver-parse-error"][0].file == (
        "torchsnapshot_tpu/broken.py"
    )
    # the healthy sibling was still scanned
    assert len(by_pass["exception-hygiene"]) == 1


def test_github_format_annotations(tmp_path, capsys):
    pkg = tmp_path / "torchsnapshot_tpu"
    pkg.mkdir()
    (pkg / "x.py").write_text(
        "def f(coord):\n    coord.kv_set('done%', '1')\n"
    )
    assert main([str(tmp_path), "--format", "github"]) == 1
    out = capsys.readouterr().out
    assert "::error file=torchsnapshot_tpu/x.py,line=2," in out
    assert "title=snaplint kv-hygiene::" in out
    assert "%25" in out  # workflow-command escaping of the literal %
    assert "::notice title=snaplint::" in out
    # clean repo: notice only, exit 0
    assert main(["--format", "github"]) == 0
    out = capsys.readouterr().out
    assert "::error" not in out


def test_format_json_alias_and_conflict(capsys):
    assert main(["--format", "json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["ok"] is True
    assert main(["--json", "--format", "github"]) == 2


def test_async_blocking_depth_cutoff_does_not_poison_memo():
    """Regression: exploring a helper at the depth cutoff must not
    cache a truncation-dependent None — a shallower caller of the same
    helper still owns its genuine blocking chain."""
    findings = _run(
        "async-blocking",
        """
        import time

        def e():
            time.sleep(1)

        def d():
            e()

        def c():
            d()

        def b():
            c()

        def a():
            b()

        async def deep():
            a()  # e sits past the chain-depth cutoff from here

        async def shallow():
            d()  # but d -> e -> time.sleep is two hops: must flag
        """,
    )
    assert [f.context for f in findings] == ["shallow"]


def test_resource_pairing_except_exception_is_not_catch_all():
    """`except Exception` misses CancelledError/KeyboardInterrupt: a
    release that lives only in that handler (plus the happy path) still
    leaks on the cancellation route — flagged.  The BaseException form
    of the same cleanup is airtight — clean."""
    flagged = _run(
        "resource-pairing",
        """
        async def one(gate, n):
            await gate.acquire(n)
            try:
                await stage()
            except Exception:
                gate.release(n)
                raise
            gate.release(n)
        """,
    )
    assert len(flagged) == 1 and "exceptional path" in flagged[0].message
    clean = _run(
        "resource-pairing",
        """
        async def one(gate, n):
            await gate.acquire(n)
            try:
                await stage()
            except BaseException:
                gate.release(n)
                raise
            gate.release(n)
        """,
    )
    assert clean == []


def test_async_blocking_result_timeout_form_flagged():
    findings = _run(
        "async-blocking",
        """
        async def f(fut):
            return fut.result(5.0)
        """,
    )
    assert len(findings) == 1
    assert ".result()" in findings[0].message


def test_resource_pairing_return_acquire_is_a_handoff():
    # a thin delegating wrapper returns the acquire itself: the caller
    # owns the release obligation
    findings = _run(
        "resource-pairing",
        """
        def reserve(self, n):
            return self._gate.acquire(n)
        """,
    )
    assert findings == []


def test_resource_pairing_result_assignment_is_not_a_handoff():
    """Regression: `etag = handle.write_part(...)` merely mentions the
    handle — the close obligation stays here, and the missing abort on
    the exceptional path must still be flagged.  Returning or storing
    the handle ITSELF remains a sanctioned transfer."""
    flagged = _run(
        "resource-pairing",
        """
        async def put(storage, path, view):
            handle = await storage.begin_striped_write(path, len(view))
            etag = await handle.write_part(0, 0, view)
            await handle.complete()
            return etag
        """,
    )
    assert len(flagged) == 1 and "striped-handle" in flagged[0].message
    clean = _run(
        "resource-pairing",
        """
        async def adopt(self, storage, path, size):
            handle = await storage.begin_striped_write(path, size)
            self._handle = handle
            return None
        """,
    )
    assert clean == []


def test_resource_pairing_return_of_derived_value_not_a_handoff():
    findings = _run(
        "resource-pairing",
        """
        def probe(self, n):
            self._gate.acquire(n)
            return self._gate.held()
        """,
    )
    assert len(findings) == 1  # the reservation still leaks


# ----------------------------------------------- live publication lint


def test_instrumentation_covers_publish_entry_points():
    """The live-publication protocol's load-bearing transitions are
    pinned into the instrumentation coverage map: a hot-swap incident
    review reconstructs publish commits (publish/record span), the
    subscriber's notice→plan→fetch→apply pass (publish/poll), and the
    swap itself (publish/apply) — none of these may be allowlisted
    away."""
    from tools.lint.passes.instrumentation import TARGETS

    pub_allow = TARGETS["torchsnapshot_tpu/publish/publisher.py"][
        "Publisher"
    ]
    assert not {
        "publish_record",
        "publish_continuous",
        "publish_snapshot",
        "publish_state",
    } & pub_allow
    sub_allow = TARGETS["torchsnapshot_tpu/publish/subscriber.py"][
        "Subscriber"
    ]
    assert "poll_once" not in sub_allow
    lw_allow = TARGETS["torchsnapshot_tpu/publish/apply.py"][
        "LiveWeights"
    ]
    assert "apply" not in lw_allow
    assert {"write_record", "read_head"} & set(
        TARGETS["torchsnapshot_tpu/publish/record.py"]["PublishStore"]
    ) == {"write_record", "read_head"}


def test_kv_hygiene_announce_without_delete_flagged():
    """Publication announce keys (the /pub/ segment — the live-weight
    publication convention) are publish-paired-with-delete: a stale
    announce would point every new subscriber at a retired publisher's
    head forever."""
    findings = _run(
        "kv-hygiene",
        """
        def announce(coord, ns, step, path):
            coord.kv_set(f"{ns}/pub/head", f"{step}:{path}")
        """,
    )
    assert len(findings) == 1
    assert "announce" in findings[0].message
    assert "kv_try_delete" in findings[0].message


def test_kv_hygiene_announce_with_module_delete_clean():
    findings = _run(
        "kv-hygiene",
        """
        def announce(coord, ns, step, path):
            coord.kv_set(f"{ns}/pub/head", f"{step}:{path}")

        def clear(coord, ns):
            coord.kv_try_delete(f"{ns}/pub/head")
        """,
    )
    assert findings == []
