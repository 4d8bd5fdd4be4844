"""Span tracer: nesting across threads/tasks, the zero-cost disabled
path, log_event composition, and the Perfetto export of a real fs-backend
take+restore roundtrip (the acceptance path for the observability layer).
"""

import json
import os
import threading

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs, obs
from torchsnapshot_tpu.obs import tracer as tracer_mod


@pytest.fixture
def traced():
    """Tracing on + a clean global tracer; restores the off default."""
    tr = obs.get_tracer()
    with knobs.override_trace(1):
        tr.reset()
        yield tr
    tr.reset()


def test_tracing_off_by_default_returns_shared_null_cm():
    assert not obs.tracing_enabled()
    # allocation-free disabled path: the SAME singleton every call, and
    # nothing recorded
    before = len(obs.get_tracer())
    assert obs.span("anything", bytes=123) is tracer_mod.NULL_CM
    with obs.span("nothing") as s:
        assert s is None
    assert len(obs.get_tracer()) == before


def test_span_nesting_and_attrs(traced):
    with obs.span("outer", a=1) as outer:
        with obs.span("inner") as inner:
            inner.attrs["late"] = True
        assert outer is not None
    spans = {s.name: s for s in traced.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["outer"].attrs == {"a": 1}
    assert spans["inner"].attrs == {"late": True}
    assert spans["inner"].start_ns >= spans["outer"].start_ns
    assert spans["inner"].end_ns <= spans["outer"].end_ns


def test_span_nesting_across_threads(traced):
    def worker():
        with obs.span("w_outer"):
            with obs.span("w_inner"):
                pass

    with obs.span("main_outer"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with obs.span("main_inner"):
            pass
    spans = {s.name: s for s in traced.spans()}
    assert spans["main_inner"].parent_id == spans["main_outer"].span_id
    assert spans["w_inner"].parent_id == spans["w_outer"].span_id
    # a fresh thread has a fresh context: no cross-thread parent leak
    assert spans["w_outer"].parent_id is None
    assert spans["w_outer"].thread_id != spans["main_outer"].thread_id


def test_error_span_records_and_flags(traced):
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (s,) = traced.spans()
    assert s.attrs.get("error") is True
    assert s.end_ns > 0


def test_begin_end_idempotent(traced):
    s = traced.begin("manual", k="v")
    traced.end(s)
    end = s.end_ns
    traced.end(s)  # second end is a no-op
    assert s.end_ns == end
    assert [sp.name for sp in traced.spans()] == ["manual"]


def test_log_event_creates_span_and_span_feeds_handlers(traced):
    from torchsnapshot_tpu.event import Event
    from torchsnapshot_tpu.event_handlers import (
        log_event,
        register_event_handler,
        unregister_event_handler,
    )

    seen = []
    handler = seen.append
    register_event_handler(handler)
    try:
        with log_event(Event("my_op", {"k": 1})):
            with obs.span("child_work", bytes=7):
                pass
    finally:
        unregister_event_handler(handler)
    # the log_event bracket became a span; the nested span parented to it
    spans = {s.name: s for s in traced.spans()}
    assert spans["child_work"].parent_id == spans["my_op"].span_id
    # the finished child span fed the handler fan-out as span/<name>;
    # the log_event bracket fired once as the event itself (no echo)
    names = [e.name for e in seen]
    assert "span/child_work" in names
    assert names.count("my_op") == 1
    assert "span/my_op" not in names


def test_max_span_cap(traced):
    old = tracer_mod._MAX_SPANS
    tracer_mod._MAX_SPANS = 5
    try:
        for i in range(8):
            with obs.span(f"s{i}"):
                pass
        assert len(traced) == 5
        assert traced.dropped == 3
    finally:
        tracer_mod._MAX_SPANS = old


def test_perfetto_overlapping_stage_spans_get_sibling_tracks(traced):
    # two concurrent staging spans must not share a tid (complete
    # events on one tid must nest); a later sequential one reuses slot 0
    a = traced.begin("pipeline/staging", idx=1)
    b = traced.begin("pipeline/staging", idx=2)
    traced.end(a)
    traced.end(b)
    c = traced.begin("pipeline/staging", idx=3)
    traced.end(c)
    doc = obs.to_trace_events(traced.spans())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    tid_by_idx = {e["args"]["idx"]: e["tid"] for e in xs}
    assert tid_by_idx[1] != tid_by_idx[2]
    assert tid_by_idx[3] == tid_by_idx[1]
    tracks = {
        e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"
    }
    assert {"pipeline/staging", "pipeline/staging #2"} <= tracks


def test_perfetto_slot_cap_bounds_track_explosion(traced):
    # admission spans all open at pipeline start: without the cap this
    # would mint one track per span and an O(n^2) scan
    spans = [traced.begin("pipeline/budget_admission", i=i) for i in range(100)]
    for s in spans:
        traced.end(s)
    doc = obs.to_trace_events(traced.spans())
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(tids) <= 32
    assert sum(1 for e in doc["traceEvents"] if e["ph"] == "X") == 100


def _containment(child, parent):
    return (
        child["ts"] >= parent["ts"]
        and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    )


def test_roundtrip_take_restore_produces_valid_perfetto_trace(tmp_path):
    """Acceptance: TORCHSNAPSHOT_TPU_TRACE=1 roundtrip against the fs
    backend yields loadable trace_event JSON with staging,
    budget-admission and storage-I/O spans, properly nested, with
    non-zero durations."""
    path = str(tmp_path / "snap")
    state = StateDict(
        w=np.arange(200000, dtype=np.float32),
        b=np.ones(1000, dtype=np.float64),
        step=7,
    )
    tr = obs.get_tracer()
    with knobs.override_trace(1):
        tr.reset()
        Snapshot.take(path, {"m": state})
        out = StateDict(
            w=np.zeros(200000, dtype=np.float32),
            b=np.zeros(1000, dtype=np.float64),
            step=0,
        )
        Snapshot(path).restore({"m": out})
        trace_path = str(tmp_path / "trace.json")
        n = obs.write_trace(trace_path)
    assert np.array_equal(out["w"], state["w"])
    assert n > 0

    with open(trace_path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    by_name: dict = {}
    for e in xs:
        by_name.setdefault(e["name"], []).append(e)

    # the three pipeline phases + both storage directions are present
    for required in (
        "pipeline/staging",
        "pipeline/budget_admission",
        "pipeline/io",
        "storage/write",
        "storage/read",
        "take",
        "restore",
    ):
        assert required in by_name, sorted(by_name)
    # non-zero durations for the real work phases
    for name in ("pipeline/staging", "pipeline/io", "storage/write",
                 "storage/read", "take", "restore"):
        assert all(e["dur"] > 0 for e in by_name[name]), name

    # span tree survives the export: storage/write nests (by parent_id
    # AND by time containment) inside a pipeline/io span
    by_id = {e["args"]["span_id"]: e for e in xs}
    nested = 0
    for e in by_name["storage/write"]:
        parent = by_id.get(e["args"]["parent_id"])
        if parent is not None and parent["name"] == "pipeline/io":
            assert _containment(e, parent)
            nested += 1
    assert nested > 0

    # async-arrow linkage: staging completion -> io start flow events
    flow_starts = {e["id"] for e in events if e["ph"] == "s"}
    flow_ends = {e["id"] for e in events if e["ph"] == "f"}
    assert flow_starts and flow_starts & flow_ends

    # one named track per pipeline stage
    track_names = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"pipeline/staging", "pipeline/io",
            "pipeline/budget_admission"} <= track_names

    # with the knob released, tracing is off again and records nothing
    assert not obs.tracing_enabled()
    tr.reset()
    Snapshot(path).restore({"m": out})
    assert len(tr) == 0


def _run_coro(coro):
    import asyncio

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_retry_backoff_spans_carry_attempt_and_verdict(traced):
    """Each resilience/backoff span names its attempt index and the
    classification verdict that triggered it; the LAST one additionally
    carries the retry sequence's final verdict."""
    from torchsnapshot_tpu.resilience.retry import (
        SharedProgress,
        classify_generic,
        retry_call,
    )

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("boom")
        return "ok"

    with knobs.override_retry_backoff_cap_s(0.001):
        progress = SharedProgress(window_s=60.0, max_attempts=5, label="t")
        out = _run_coro(
            retry_call(
                flaky, op_name="op", backend="testbe",
                classify=classify_generic, progress=progress,
            )
        )
    assert out == "ok"
    backoffs = [
        s for s in traced.spans() if s.name == "resilience/backoff"
    ]
    assert [s.attrs["attempt"] for s in backoffs] == [1, 2]
    assert all(s.attrs["verdict"] == "transient" for s in backoffs)
    assert all(s.attrs["backend"] == "testbe" for s in backoffs)
    assert backoffs[-1].attrs["final_verdict"] == "success"
    assert "final_verdict" not in backoffs[0].attrs


def test_retry_exhaustion_stamps_final_verdict(traced):
    from torchsnapshot_tpu.resilience.retry import (
        SharedProgress,
        classify_generic,
        retry_call,
    )

    def doomed():
        raise ConnectionError("always")

    with knobs.override_retry_backoff_cap_s(0.001):
        progress = SharedProgress(window_s=60.0, max_attempts=2, label="t2")
        with pytest.raises(ConnectionError):
            _run_coro(
                retry_call(
                    doomed, op_name="op", backend="testbe",
                    classify=classify_generic, progress=progress,
                )
            )
    backoffs = [
        s for s in traced.spans() if s.name == "resilience/backoff"
    ]
    assert backoffs
    assert backoffs[-1].attrs["final_verdict"] == "exhausted"


def test_striped_write_per_part_slices_and_flow_arrows(tmp_path, traced):
    """Perfetto keeps per-PART granularity for striped writes: each
    stripe/stage_part slice carries a flow arrow to its matching
    stripe/write_part slice, and part slices land on stripe stage
    tracks (interval-partitioned) instead of thread tracks."""
    path = str(tmp_path / "snap")
    with knobs.override_stripe_part_size_bytes(1 << 16), (
        knobs.override_stripe_min_object_size_bytes(1 << 16)
    ):
        Snapshot.take(
            path,
            {"app": StateDict(w=np.arange(1 << 18, dtype=np.float32))},
        )
    spans = traced.spans()
    stage = [s for s in spans if s.name == "stripe/stage_part"]
    write = [s for s in spans if s.name == "stripe/write_part"]
    assert len(stage) == 16 and len(write) == 16
    # one arrow per part: stage flow_out pairs with write flow_in
    by_part_out = {s.attrs["part"]: s.flow_out for s in stage}
    by_part_in = {s.attrs["part"]: s.flow_in for s in write}
    assert by_part_out == by_part_in
    assert all(fid is not None for fid in by_part_out.values())
    doc = obs.to_trace_events(spans)
    events = doc["traceEvents"]
    # per-part slices on stripe tracks, not thread tracks
    tracks = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert any(t.startswith("stripe/write_part") for t in tracks)
    assert any(t.startswith("stripe/stage_part") for t in tracks)
    # every part arrow survives the export as a matched s/f pair
    flow_starts = {e["id"] for e in events if e["ph"] == "s"}
    flow_ends = {e["id"] for e in events if e["ph"] == "f"}
    assert set(by_part_out.values()) <= (flow_starts & flow_ends)


def test_cli_trace_command(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(x=np.arange(64.0), n=1)})
    out = str(tmp_path / "out.json")
    rc = main(["trace", path, "--out", out])
    assert rc == 0
    assert "wrote" in capsys.readouterr().out
    doc = json.load(open(out))
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "storage/read" in names and "materialize" in names
    assert not obs.tracing_enabled()  # CLI restored the knob


# ------------------------------------------------------------ executor hop


def _walk_to_root(span, by_id):
    while span.parent_id is not None:
        span = by_id[span.parent_id]
    return span


def test_hop_splits_queue_wait_from_work_and_links_the_parent(traced):
    """A one-worker pool held by a blocker: the hop's worker span starts
    when the worker picks it up, ``queue_ns`` holds the wait before that,
    and its parent is the span that submitted it on the loop thread."""
    import asyncio
    import time
    from concurrent.futures import ThreadPoolExecutor

    release = threading.Event()
    pool = ThreadPoolExecutor(1, thread_name_prefix="tsnp-test")

    def work(x):
        time.sleep(0.02)
        return x + 1

    async def main():
        blocker = pool.submit(release.wait)
        with obs.span("loop_side") as submitting:
            fut = obs.run_in_executor(pool, work, 41, name="consume/test", nbytes=7)
            await asyncio.sleep(0.08)
            release.set()
            out = await fut
        blocker.result()
        return out, submitting

    try:
        out, submitting = _run_coro(main())
    finally:
        release.set()
        pool.shutdown()
    assert out == 42
    spans = {s.name: s for s in traced.spans()}
    hop = spans["consume/test"]
    assert hop.parent_id == submitting.span_id
    assert hop.thread_name.startswith("tsnp-test") and hop.thread_id != submitting.thread_id
    assert hop.attrs["bytes"] == 7
    assert hop.attrs["queue_ns"] >= 70e6  # held for 80 ms before a worker was free
    assert 15e6 <= hop.duration_ns < hop.attrs["queue_ns"]  # the work alone
    # the wait lies before the span, not inside it
    assert hop.start_ns - submitting.start_ns >= hop.attrs["queue_ns"] * 0.9


def test_hop_with_tracing_off_is_the_plain_call(monkeypatch):
    """Off: one flag read, then ``loop.run_in_executor(executor, fn, *args)``
    itself — no span, no clock read, no context copy, no wrapper on the
    worker."""
    import asyncio
    from concurrent.futures import ThreadPoolExecutor

    assert not obs.tracing_enabled()

    def forbidden(*_a, **_k):
        raise AssertionError("the disabled hop touched the tracer's machinery")

    class NoClock:
        monotonic_ns = staticmethod(forbidden)
        monotonic = perf_counter = staticmethod(forbidden)

    monkeypatch.setattr(tracer_mod, "time", NoClock)
    monkeypatch.setattr(tracer_mod, "copy_context", forbidden)
    monkeypatch.setattr(tracer_mod, "_hop", forbidden)
    monkeypatch.setattr(tracer_mod, "Span", forbidden)
    submitted = []
    pool = ThreadPoolExecutor(1)

    async def main():
        loop = asyncio.get_running_loop()
        plain = loop.run_in_executor

        def spy(executor, fn, *args):
            submitted.append((executor, fn, args))
            return plain(executor, fn, *args)

        loop.run_in_executor = spy
        fut = obs.run_in_executor(pool, divmod, 17, 5, name="consume/test", nbytes=1)
        assert isinstance(fut, asyncio.Future)
        return await fut

    before = len(obs.get_tracer())
    try:
        assert _run_coro(main()) == (3, 2)
    finally:
        pool.shutdown()
    assert submitted == [(pool, divmod, (17, 5))]  # exactly today's call
    assert len(obs.get_tracer()) == before


def _jax_state(seed, n=6):
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import PyTreeState

    key = jax.random.PRNGKey(seed)
    return PyTreeState({
        # past the inline-consume threshold, so every leaf takes the hop
        f"w{i}": jax.random.normal(jax.random.fold_in(key, i), (512, 256), jnp.float32)
        for i in range(n)
    })


@pytest.mark.parametrize("op", ["take", "async_take", "restore"])
def test_every_span_of_a_call_reaches_its_root(tmp_path, op):
    """Worker threads (the staging and consume pools, the fs plugin's pool)
    and the loop thread record under the API bracket of the call that made
    them: the root's span_id names the request."""
    path = str(tmp_path / "snap")
    tr = obs.get_tracer()
    if op == "restore":
        Snapshot.take(path, {"ts": _jax_state(0), "meta": StateDict(step=3)})
    with knobs.override_trace(1):
        tr.reset()
        if op == "take":
            Snapshot.take(path, {"ts": _jax_state(0), "meta": StateDict(step=3)})
        elif op == "async_take":
            Snapshot.async_take(
                path, {"ts": _jax_state(0), "meta": StateDict(step=3)}
            ).wait()
        else:
            out = {"ts": _jax_state(1), "meta": StateDict(step=-1)}
            Snapshot(path).restore(out)
            assert out["meta"]["step"] == 3
        spans = tr.spans()
    tr.reset()
    by_id = {s.span_id: s for s in spans}
    roots = [s for s in spans if s.name == op and s.parent_id is None]
    assert len(roots) == 1
    off_caller = [s for s in spans if s.thread_id != roots[0].thread_id]
    pools = {s.thread_name.rsplit("_", 1)[0] for s in off_caller}
    want = {"tsnp-consume", "tsnp-read-loop"} if op == "restore" else {"tsnp-staging", "tsnp-io-loop"}
    assert want <= pools and "tsnp-fsio" in pools, pools
    for s in off_caller:
        assert _walk_to_root(s, by_id) is roots[0], (s.name, s.thread_name)
    names = {s.name for s in spans}
    if op == "restore":
        assert {"restore/metadata", "restore/plan", "restore/pipeline",
                "restore/finalize", "consume/materialize", "h2d/put",
                "storage/attempt"} <= names
        hops = [s for s in spans if s.name == "consume/materialize"]
        assert all(s.attrs["queue_ns"] >= 0 and s.attrs["bytes"] > 0 for s in hops)
        assert all(by_id[s.parent_id].name == "pipeline/consume" for s in hops)
        puts = [s for s in spans if s.name == "h2d/put"]
        assert all(by_id[s.parent_id].name == "consume/materialize" for s in puts)
    else:
        assert {"take/plan", "stage/materialize", "d2h/copy", "stage/digest"} <= names
        if op == "take":
            assert {"take/pipeline", "take/commit"} <= names


def _tsnp_events_inside(trace_dir, mark):
    """``tsnp:`` events of the profile under ``trace_dir`` that lie inside
    the host annotation ``mark``: [(name, stats)]."""
    import glob

    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    assert found, os.listdir(trace_dir)
    events = [
        ev for plane in ProfileData.from_file(found[-1]).planes
        for line in plane.lines for ev in line.events
    ]
    marks = [ev for ev in events if ev.name == mark]
    assert len(marks) == 1
    lo, hi = marks[0].start_ns, marks[0].start_ns + marks[0].duration_ns
    return [
        (ev.name, dict(ev.stats)) for ev in events
        if ev.name.startswith("tsnp:") and lo <= ev.start_ns <= hi
    ]


@pytest.mark.parametrize("tracing", [1, 0], ids=["tracing_on", "tracing_off"])
def test_spans_sit_on_the_profilers_clock(tmp_path, tracing):
    """Inside a ``jax.profiler`` session a traced restore's lexical spans are
    ``tsnp:`` events of the same xplane, each naming its thread; with
    tracing off the program adds none."""
    import jax

    path = str(tmp_path / "snap")
    Snapshot.take(path, {"ts": _jax_state(0)})
    out = {"ts": _jax_state(1)}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    trace_dir = str(tmp_path / "trace")
    tr = obs.get_tracer()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with knobs.override_trace(tracing):
            tr.reset()
            with jax.profiler.TraceAnnotation("test:restore"):
                Snapshot(path).restore(out)
            recorded = {s.name for s in tr.spans()}
    finally:
        jax.profiler.stop_trace()
        tr.reset()
    events = _tsnp_events_inside(trace_dir, "test:restore")
    if not tracing:
        assert events == [] and recorded == set()
        return
    names = {name for name, _ in events}
    assert {"tsnp:restore", "tsnp:restore/pipeline", "tsnp:h2d/put",
            "tsnp:consume/materialize"} <= names
    # begin/end spans cross threads and stay on the monotonic clock alone
    assert "pipeline/budget_admission" in recorded
    assert "tsnp:pipeline/budget_admission" not in names
    for name, stats in events:
        if name == "tsnp:h2d/put":
            assert stats["thread"].startswith("tsnp-consume") and stats["bytes"] > 0
        if name == "tsnp:consume/materialize":
            assert stats["queue_ns"] >= 0
        if name == "tsnp:restore/pipeline":
            assert stats["thread"] == "MainThread" and stats["workers"] >= 1


def _profiled(tmp_path, run, tracing=1):
    """Run ``run()`` inside a ``jax.profiler`` session with tracing as
    given: the recorded spans, and the profile's ``tsnp:`` events as
    [(name, stats)]."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    trace_dir = str(tmp_path / "trace")
    tr = obs.get_tracer()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with knobs.override_trace(tracing):
            tr.reset()
            run()
            spans = tr.spans()
    finally:
        jax.profiler.stop_trace()
        tr.reset()
    (found,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = [
        (ev.name, dict(ev.stats)) for plane in ProfileData.from_file(found).planes
        for line in plane.lines for ev in line.events if ev.name.startswith("tsnp:")
    ]
    return spans, events


@pytest.mark.parametrize("op", ["take", "restore"])
def test_every_lexical_span_has_one_event_with_its_id(tmp_path, op):
    """Each recorded lexical span of a traced take or restore is exactly one
    ``tsnp:`` event of the profile, named as the span and carrying its
    ``span_id`` and ``parent_id``; a ``begin``/``end`` span is none."""
    path = str(tmp_path / "snap")
    if op == "restore":
        Snapshot.take(path, {"ts": _jax_state(0)})
        spans, events = _profiled(tmp_path, lambda: Snapshot(path).restore({"ts": _jax_state(1)}))
    else:
        spans, events = _profiled(tmp_path, lambda: Snapshot.take(path, {"ts": _jax_state(0)}))
    by_id = {}
    for name, stats in events:
        by_id.setdefault(stats["span_id"], []).append((name, stats))
    lexical = [s for s in spans if s.lexical]
    assert {op, f"{op}/pipeline"} <= {s.name for s in lexical}
    assert "pipeline/budget_admission" in {s.name for s in spans if not s.lexical}
    for s in lexical:
        ((name, stats),) = by_id[s.span_id]
        assert name == "tsnp:" + s.name and stats.get("parent_id") == s.parent_id
        assert stats["thread"] == s.thread_name
    assert set(by_id) == {s.span_id for s in lexical}


def _reshard_restore(tmp_path):
    """A column leaf saved under 2x2 and restored under 1x4 on the direct
    path: a piece put once, cut, and half handed to a sibling device."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def put(value, dp, tp):
        mesh = Mesh(np.array(jax.devices()[: dp * tp]).reshape(dp, tp), ("dp", "tp"))
        return jax.device_put(value, NamedSharding(mesh, P(None, "tp")))

    value = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    path = str(tmp_path / "resharded")
    Snapshot.take(path, {"app": StateDict(w=put(value, 2, 2))})
    dest = StateDict(w=put(np.zeros_like(value), 1, 4))
    return lambda: Snapshot(path).restore({"app": dest})


# the spans that hold a host-side wait for a transfer, or the call that
# issues one, by the path that opens them (chunk/put and chunk/land:
# tests/test_chunked_device_restore.py)
LINKS = {
    "restore": {"h2d/put": "h2d"},
    "take": {"d2h/copy": "d2h"},
    "device_unpack": {"h2d/put": "h2d", "unpack/dispatch": "h2d"},
    "reshard": {"reshard/direct": "h2d", "h2d/put": "h2d", "d2d/put": "d2d"},
}


@pytest.mark.parametrize("case", sorted(LINKS))
def test_the_spans_that_wait_on_the_link_say_which_way(tmp_path, case):
    path = str(tmp_path / "snap")
    tr = obs.get_tracer()
    if case != "take":
        Snapshot.take(path, {"ts": _jax_state(0)})
    run = {
        "take": lambda: Snapshot.take(path, {"ts": _jax_state(0)}),
        "restore": lambda: Snapshot(path).restore({"ts": _jax_state(1)}),
        "device_unpack": lambda: Snapshot(path).restore({"ts": _jax_state(1, n=12)}),
    }.get(case) or _reshard_restore(tmp_path)
    if case == "device_unpack":
        # small leaves, so that the take writes them as one slab
        Snapshot.take(path, {"ts": _jax_state(0, n=12)})
    with knobs.override_device_unpack(case in ("device_unpack", "reshard")), \
            knobs.override_trace(1):
        tr.reset()
        run()
        spans = tr.spans()
    tr.reset()
    for name, link in LINKS[case].items():
        found = [s for s in spans if s.name == name]
        assert found, (name, {s.name for s in spans})
        assert {s.attrs.get("link") for s in found} == {link}, name
    tagged = {s.name for s in spans if "link" in s.attrs}
    assert tagged <= {"h2d/put", "d2h/copy", "unpack/dispatch", "reshard/direct",
                      "d2d/put", "chunk/put", "chunk/land"}


def test_take_batch_and_commit_drain_lie_where_the_take_does_that_work(tmp_path, traced):
    Snapshot.take(str(tmp_path / "snap"), {"ts": _jax_state(0, n=12), "meta": StateDict(step=3)})
    spans = traced.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (root,) = by_name["take"]
    (plan,), (batch,), (pipeline,) = by_name["take/plan"], by_name["take/batch"], by_name["take/pipeline"]
    (commit,), (drain,) = by_name["take/commit"], by_name["commit/drain"]
    assert batch.parent_id == root.span_id and drain.parent_id == commit.span_id
    assert plan.end_ns <= batch.start_ns and batch.end_ns <= pipeline.start_ns
    assert commit.start_ns <= drain.start_ns and drain.end_ns <= commit.end_ns
    # the twelve small leaves go as one slab; the primitive rides the metadata
    assert batch.attrs == {"objects": 1, "slabs": 1}
    assert drain.attrs == {}


def test_with_tracing_off_the_new_spans_leave_no_trace(tmp_path):
    """With tracing off a take (take/batch, commit/drain) and a restore
    record nothing and add no event to a profile (chunk/land:
    tests/test_chunked_device_restore.py)."""
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"ts": _jax_state(0)})

    def both():
        Snapshot.take(str(tmp_path / "again"), {"ts": _jax_state(0)})
        Snapshot(path).restore({"ts": _jax_state(1)})

    spans, events = _profiled(tmp_path, both, tracing=0)
    assert spans == [] and events == []
