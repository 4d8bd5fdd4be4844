"""The nine readers of the program's spans (``chipbench/metrics``): on a
hand-built context, where a stall planted in one phase of a restore moves
that phase's metric and no other, and on a whole traced run at small widths
on the CPU, where the caller-thread metrics have to tile a restore."""

import json
import os
import shutil

import pytest

from chipbench import bench
from torchsnapshot_tpu.obs.tracer import Span

CELL = "ouro-2.6b-d9.kill_resume"
READERS = [
    "restore.plan_s", "restore.pipeline_s", "restore.finalize_s",
    "restore.tail_wait_s", "consume.queue_s", "consume.work_s",
    "consume.pool_busy_share", "h2d.put_s", "unpack.dispatch_s",
]
MS = 1_000_000


@pytest.fixture(scope="module")
def cell(repo):
    return bench.Cell(repo, CELL)


def test_benchmark_json_lists_the_nine_readers_for_the_cell(cell):
    listed = {m["name"]: m for m in cell.per_layer_metrics()}
    for name in READERS:
        assert listed[name]["moves"] == "resume_s" and listed[name]["workloads"] == [CELL]
        assert callable(cell.reader(name))


# ------------------------------------------------------ a hand-built window


def _span(spans, name, parent, start_ms, ms, thread="MainThread", **attrs):
    s = Span(name, parent.span_id if parent is not None else None, attrs)
    s.thread_name = thread
    s.start_ns, s.end_ns = int(start_ms * MS), int((start_ms + ms) * MS)
    spans.append(s)
    return s


def _restore(spans, timeline, at_ms, stall=None, partitioned=True, unpack=True):
    """One restore as the program records it: 10 ms metadata, 5 ms plan, a
    pipeline of two consume tasks on a pool of 4, 4 ms finalize, 3 ms of
    waiting after the program returned.  ``stall`` adds 1000 ms to one phase
    and moves everything after it."""
    add = {k: 1000 if k == stall else 0 for k in (
        "metadata", "plan", "pipeline", "finalize", "tail", "queue", "work", "put", "dispatch",
    )}
    t = at_ms + 1
    root = _span(spans, "restore", None, t, 0)
    if partitioned:
        t = _span(spans, "restore/metadata", root, t, 10 + add["metadata"]).end_ns / MS
    # the commits before PR 26 bracket a key's load in one span
    key = root if partitioned else _span(spans, "restore/load_stateful", root, t, 0)
    if partitioned:
        t = _span(spans, "restore/plan", key, t, 5 + add["plan"], leaves=2, reads=2).end_ns / MS
        pipe = _span(spans, "restore/pipeline", key, t, 0, workers=4, reads=2)
    else:
        pipe = key
    task = _span(spans, "pipeline/consume", pipe, t + 1, 0, thread="tsnp-read-loop")
    queue = 7 + add["queue"]
    w0 = t + 1 + queue
    put, dispatch = 20 + add["put"], (2 + add["dispatch"]) if unpack else 0
    work = 1 + put + dispatch + add["work"]
    if partitioned:
        worker = _span(
            spans, "consume/unpack" if unpack else "consume/materialize", task,
            w0, work, thread="tsnp-consume_0", queue_ns=queue * MS, bytes=1 << 20,
        )
        _span(spans, "h2d/put", worker, w0 + 1, put, thread="tsnp-consume_0", bytes=1 << 20)
        if unpack:
            _span(spans, "unpack/dispatch", worker, w0 + 1 + put, dispatch,
                  thread="tsnp-consume_0", members=3)
        _span(spans, "consume/materialize", task, t + 2, 30, thread="tsnp-consume_1",
              queue_ns=1 * MS, bytes=1 << 22)
    task.end_ns = int((w0 + work) * MS)
    t = w0 + work + 2 + add["pipeline"]
    if partitioned:
        pipe.end_ns = int(t * MS)
        t = _span(spans, "restore/finalize", key, t, 4 + add["finalize"]).end_ns / MS
    key.end_ns = root.end_ns = int(t * MS)
    end = t + 3 + add["tail"]
    timeline.append({"op": "template", "t0": at_ms / 1e3 - 0.004, "t1": at_ms / 1e3})
    timeline.append({"op": "restore", "t0": at_ms / 1e3, "t1": end / 1e3})
    return end


def _context(stall=None, restores=2, **kw):
    spans, timeline, at = [], [], 5000.0
    for i in range(restores):
        at = _restore(spans, timeline, at, stall=stall if i == 1 else None, **kw) + 10
    # a span outside every restore record belongs to no restore
    _span(spans, "h2d/put", None, at + 50, 500)
    return bench.Context(timeline=timeline, spans=spans)


def _read_all(cell, ctx):
    return {name: cell.reader(name)(ctx) for name in READERS}


def test_readers_on_a_hand_built_window(cell):
    got = _read_all(cell, _context())
    assert got["restore.plan_s"] == pytest.approx(0.015)
    assert got["restore.finalize_s"] == pytest.approx(0.004)
    assert got["restore.tail_wait_s"] == pytest.approx(0.003)
    assert got["consume.queue_s"] == pytest.approx(0.008)
    assert got["consume.work_s"] == pytest.approx(0.023 + 0.030)
    assert got["h2d.put_s"] == pytest.approx(0.020)
    assert got["unpack.dispatch_s"] == pytest.approx(0.002)
    assert got["restore.pipeline_s"] == pytest.approx(0.001 + 0.007 + 0.023 + 0.002)
    assert got["consume.pool_busy_share"] == pytest.approx(0.053 / (4 * 0.033))


# the phase a stall sits in, and the metrics that have to move with it: the
# phase's own, and those that hold its span (a put lengthens its worker's
# span; the pool's share is work over the pipeline's wall)
STALLS = {
    "metadata": {"restore.plan_s"},
    "plan": {"restore.plan_s"},
    "pipeline": {"restore.pipeline_s", "consume.pool_busy_share"},
    "finalize": {"restore.finalize_s"},
    "tail": {"restore.tail_wait_s"},
    "queue": {"consume.queue_s", "restore.pipeline_s", "consume.pool_busy_share"},
    "work": {"consume.work_s", "restore.pipeline_s", "consume.pool_busy_share"},
    "put": {"h2d.put_s", "consume.work_s", "restore.pipeline_s", "consume.pool_busy_share"},
    "dispatch": {"unpack.dispatch_s", "consume.work_s", "restore.pipeline_s",
                 "consume.pool_busy_share"},
}


OWN = {
    "metadata": "restore.plan_s", "plan": "restore.plan_s",
    "pipeline": "restore.pipeline_s", "finalize": "restore.finalize_s",
    "tail": "restore.tail_wait_s", "queue": "consume.queue_s",
    "work": "consume.work_s", "put": "h2d.put_s", "dispatch": "unpack.dispatch_s",
}


@pytest.mark.parametrize("phase", sorted(STALLS))
def test_a_planted_stall_moves_its_phase_and_no_other(cell, phase):
    clean, stalled = _read_all(cell, _context()), _read_all(cell, _context(stall=phase))
    moved = {n for n in READERS if stalled[n] != pytest.approx(clean[n], abs=1e-9)}
    assert moved == STALLS[phase]
    own = OWN[phase]
    # one second in one of two restores: half a second per restore
    assert stalled[own] - clean[own] == pytest.approx(0.5)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_without_a_restore(cell, name):
    assert cell.reader(name)(_context(restores=0)) is None
    assert cell.reader(name)(bench.Context(timeline=[], spans=[])) is None
    # restores in the timeline, but the run was not traced
    timeline = _context().timeline
    assert cell.reader(name)(bench.Context(timeline=timeline, spans=[])) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_that_does_not_partition_its_restores(cell, name):
    """The commits before PR 26 record the root and nothing a reader names:
    all leave their metric out but the tail wait, which needs the root alone."""
    value = cell.reader(name)(_context(partitioned=False))
    if name == "restore.tail_wait_s":
        assert value == pytest.approx(0.003)
    else:
        assert value is None


def test_a_phase_that_did_not_occur_reads_zero(cell):
    got = _read_all(cell, _context(unpack=False))
    assert got["unpack.dispatch_s"] == 0.0
    assert got["h2d.put_s"] == pytest.approx(0.020)


# ------------------------------------------------- a whole traced run, on CPU

SMALL = dict(
    hidden_size=512, num_attention_heads=4, num_key_value_heads=4, head_dim=128,
    intermediate_size=1024, vocab_size=4096, num_hidden_layers=4,
    max_position_embeddings=64,
)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory, repo, benchmark_json):
    """One traced run of the cell at widths where a restore takes long
    enough on a CPU (a tenth of a second) for 2% of it to mean something."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(repo, "chipbench"), os.path.join(root, "chipbench"))
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), root)

    def rewrite(path, change):
        with open(path) as f:
            data = json.load(f)
        change(data)
        with open(path, "w") as f:
            json.dump(data, f)

    for config in benchmark_json["configs"]:
        rewrite(os.path.join(root, config["file"]), lambda c: c.update(SMALL))
    mix = os.path.join(root, "chipbench", "traffic", "kill_resume.json")
    # eight restores, whatever a loaded worker's clock says
    rewrite(mix, lambda m: m.update(
        batch=[2, 16], check={"loops": 2, "below": 3}, answers_checked_least=3,
        window={"loop": ["restore"], "max_loops": 8},
    ))
    seen = {}
    context = bench.Context

    def keep(**fields):
        seen["ctx"] = context(**fields)
        return seen["ctx"]

    bench.Context = keep
    try:
        result = bench.run_cell(root, CELL, seed=2**31 + 11, seconds=3600.0, trace=True, allow_cpu=True)
    finally:
        bench.Context = context
    return result, seen["ctx"]


def test_the_caller_thread_metrics_tile_a_restore(traced_run):
    result, ctx = traced_run
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(READERS) <= set(m)
    records = [r for r in ctx.timeline if r["op"] == "restore"]
    mean = sum(r["t1"] - r["t0"] for r in records) / len(records)
    tiled = (
        m["restore.plan_s"] + m["restore.pipeline_s"]
        + m["restore.finalize_s"] + m["restore.tail_wait_s"]
    )
    assert tiled <= mean
    assert tiled == pytest.approx(mean, rel=0.02)


def test_the_pool_cannot_work_more_than_its_threads(traced_run):
    result, ctx = traced_run
    m = {k: v["value"] for k, v in result["metrics"].items()}
    workers = max(s.attrs["workers"] for s in ctx.spans if s.name == "restore/pipeline")
    assert 0 < m["consume.work_s"] <= workers * m["restore.pipeline_s"]
    assert 0 < m["consume.pool_busy_share"] <= 1
    assert 0 < m["h2d.put_s"] <= m["consume.work_s"]
    assert m["consume.queue_s"] >= 0
    # the device unpack is off for CPU arrays by the program's own choice
    assert m["unpack.dispatch_s"] == 0.0
    assert m["device_unpack.calls"] == 0.0
    # task-seconds in flight hold the wait for a worker and the work
    assert m["consume.queue_s"] + m["consume.work_s"] <= m["consume.busy_s"] * 1.02


def test_spans_of_a_window_stay_under_the_recorder_cap(traced_run):
    from torchsnapshot_tpu.obs import tracer

    _, ctx = traced_run
    assert tracer.get_tracer().dropped == 0
    assert 0 < len(ctx.spans) < tracer._MAX_SPANS


# ------------------------------------------------- the save side: per take

SAVE_CELL = "ouro-2.6b-d9.preempt_sync_save"
SAVE_READERS = ["plan.take_s", "stage.queue_s", "d2h.copy_s", "stage.digest_s"]


@pytest.fixture(scope="module")
def save_cell(repo):
    return bench.Cell(repo, SAVE_CELL)


def test_benchmark_json_lists_the_save_readers_for_the_sync_cell(save_cell):
    listed = {m["name"]: m for m in save_cell.per_layer_metrics()}
    for name in SAVE_READERS:
        assert listed[name]["moves"] == "save_commit_s"
        assert listed[name]["workloads"] == [SAVE_CELL]
        assert callable(save_cell.reader(name))


def _take(spans, timeline, at_ms, stall=None, piped=True, asynchronous=False):
    """One blocking take as the program records it: 2 ms of plan, then a
    pipeline whose staging pool materializes one slab (7 ms in the queue,
    30 ms of work, 20 of them in the copy off the device), copies one host
    array (1 ms, 5 ms) and digests both (2 ms in the queue and 4 ms each).
    ``stall`` adds 1000 ms to one phase and moves everything after it."""
    add = {k: 1000 if k == stall else 0 for k in ("plan", "queue", "d2h", "digest")}
    t = at_ms + 1
    t = _span(spans, "take/plan", None, t, 2 + add["plan"], leaves=2, rank=0).end_ns / MS
    pipe = _span(spans, "take/pipeline", None, t, 0, workers=4, writes=2) if piped else None
    queue = 7 + add["queue"]
    work = 30 + add["d2h"]
    stage = _span(spans, "stage/materialize", pipe, t + queue, work,
                  thread="tsnp-stage_0", queue_ns=queue * MS, bytes=1 << 22)
    _span(spans, "d2h/copy", stage, t + queue + 5, 20 + add["d2h"], thread="tsnp-stage_0",
          bytes=1 << 22)
    _span(spans, "stage/copy", pipe, t + 1, 5, thread="tsnp-stage_1", queue_ns=1 * MS,
          bytes=1 << 20)
    t = stage.end_ns / MS
    for _ in range(2):
        t = _span(spans, "stage/digest", pipe, t + 2, 4 + add["digest"] / 2,
                  thread="tsnp-stage_0", queue_ns=2 * MS, bytes=1 << 20).end_ns / MS
    if pipe is not None:
        pipe.end_ns = int((t + 3) * MS)
    end = t + 5
    timeline.append({"op": "step", "t0": at_ms / 1e3 - 0.01, "t1": at_ms / 1e3})
    record = {"op": "take", "t0": at_ms / 1e3, "t1": end / 1e3}
    if asynchronous:
        record["asynchronous"] = True
    timeline.append(record)
    return end


def _save_context(stall=None, takes=2, **kw):
    spans, timeline, at = [], [], 7000.0
    for i in range(takes):
        at = _take(spans, timeline, at, stall=stall if i == 1 else None, **kw) + 15
    # a copy off the device outside every take belongs to no save
    _span(spans, "d2h/copy", None, at + 50, 500)
    return bench.Context(timeline=timeline, spans=spans)


def _read_saves(cell, ctx):
    return {name: cell.reader(name)(ctx) for name in SAVE_READERS}


def test_save_readers_on_a_hand_built_window(save_cell):
    got = _read_saves(save_cell, _save_context())
    assert got["plan.take_s"] == pytest.approx(0.002)
    assert got["stage.queue_s"] == pytest.approx(0.007 + 0.001 + 2 * 0.002)
    assert got["d2h.copy_s"] == pytest.approx(0.020)
    assert got["stage.digest_s"] == pytest.approx(0.008)


SAVE_STALLS = {
    "plan": "plan.take_s", "queue": "stage.queue_s",
    "d2h": "d2h.copy_s", "digest": "stage.digest_s",
}


@pytest.mark.parametrize("phase", sorted(SAVE_STALLS))
def test_a_stall_planted_in_a_save_moves_its_reader_and_no_other(save_cell, phase):
    clean = _read_saves(save_cell, _save_context())
    stalled = _read_saves(save_cell, _save_context(stall=phase))
    moved = {n for n in SAVE_READERS if stalled[n] != pytest.approx(clean[n], abs=1e-9)}
    own = SAVE_STALLS[phase]
    assert moved == {own}
    # one second in one of two takes: half a second per save
    assert stalled[own] - clean[own] == pytest.approx(0.5)


@pytest.mark.parametrize("name", SAVE_READERS[1:])
def test_a_save_reader_finds_nothing_without_a_blocking_take(save_cell, name):
    read = save_cell.reader(name)
    assert read(_save_context(takes=0)) is None
    assert read(bench.Context(timeline=[], spans=[])) is None
    # takes in the timeline, but the run was not traced
    assert read(bench.Context(timeline=_save_context().timeline, spans=[])) is None
    # a program that records no pipeline (the commits before PR 26)
    assert read(_save_context(piped=False)) is None
    # the call of an async_take returns before its spans: not a save here
    assert read(_save_context(asynchronous=True)) is None


def test_a_restore_window_has_no_save_and_a_save_window_no_restore(cell, save_cell):
    restores, saves = _context(), _save_context()
    for name in SAVE_READERS[1:]:
        assert save_cell.reader(name)(restores) is None
    for name in READERS:
        assert cell.reader(name)(saves) is None
