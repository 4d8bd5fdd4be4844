"""``tools/tsnp_take_timeline.py``: the per-save span table, on spans made
by hand (the run that records real ones needs a cell and a chip)."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools import tsnp_take_timeline as tl  # noqa: E402

MS = 1_000_000


def _span(name, start_ms, end_ms, thread="tsnp-staging_0", **attrs):
    return SimpleNamespace(
        name=name, start_ns=start_ms * MS, end_ns=end_ms * MS,
        duration_ns=(end_ms - start_ms) * MS, thread_name=thread, attrs=attrs,
    )


def _save(t0, digest_at):
    """One save of two objects from ``t0`` ms: copies 10..110 and 10..210,
    the first object's checksum at ``digest_at``."""
    return [
        _span("take/plan", t0 - 5, t0 - 1, "MainThread"),
        _span("take/pipeline", t0, t0 + 300, "MainThread", workers=2),
        _span("stage/materialize", t0 + 10, t0 + 110, queue_ns=2 * MS, bytes=100),
        _span("d2h/copy", t0 + 11, t0 + 110, bytes=100),
        _span("stage/materialize", t0 + 10, t0 + 210, "tsnp-staging_1", queue_ns=3 * MS, bytes=200),
        _span("stage/digest", t0 + digest_at, t0 + digest_at + 20, queue_ns=(digest_at - 110) * MS, bytes=100),
        _span("pipeline/io", t0 + 250, t0 + 290, "tsnp-io-loop", path="0/batched.0", bytes=100),
        _span("take/commit", t0 + 301, t0 + 320, "MainThread"),
        _span("some/other", t0 + 5, t0 + 6),
    ]


def test_spans_are_split_by_save_and_summed_by_name():
    spans = _save(1000, digest_at=210) + _save(2000, digest_at=111)
    saves = tl.saves(spans)
    assert [len(s) for s in saves] == [8, 8]  # ``some/other`` is no span of a save
    assert all(995 * MS <= s.start_ns < 1995 * MS for s in saves[0])
    rows = {line.split()[0]: line for line in tl.table(saves[1])}
    assert "n=  2" in rows["stage/materialize"]
    assert "first     10.0 last    210.0 ms" in rows["stage/materialize"]
    assert "work   0.300 s  queued    0.005 s" in rows["stage/materialize"]
    assert "first     -5.0" in rows["take/plan"]
    assert "last    320.0 ms" in rows["take/commit"]


@pytest.mark.parametrize("digest_at, queued", [(210, "0.100"), (111, "0.001")])
def test_the_table_shows_where_a_checksum_waited(digest_at, queued):
    # a checksum queued behind every copy starts where the last one ends;
    # one that is next in line starts where its own object's copy ends
    rows = {line.split()[0]: line for line in tl.table(tl.saves(_save(0, digest_at))[0])}
    assert f"first {digest_at:8.1f}" in rows["stage/digest"]
    assert f"queued {queued:>8} s" in rows["stage/digest"]


def test_the_report_lists_one_save_span_by_span():
    spans = _save(1000, 210) + _save(2000, 111)
    lines = tl.report(spans, nth=1)
    assert lines[0] == "save 0: pipeline 300 ms"
    listed = [line for line in lines if "queued_ms=" in line]
    assert len(listed) == 8  # save 1 alone
    assert "0/batched.0" in next(line for line in listed if "pipeline/io" in line)
    starts = [float(line.split()[0]) for line in listed]
    assert starts == sorted(starts) and starts[0] == -5.0
    assert tl.report([], nth=0)[0].startswith("no take/pipeline span")
