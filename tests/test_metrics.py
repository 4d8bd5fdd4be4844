"""Metrics registry: instrument semantics, histogram bucketing,
snapshot/reset, hot-path integration (take/restore populate the
registry), the rss_profiler gauge, and the CLI `stats` command on a real
snapshot.
"""

import json
import threading

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, obs
from torchsnapshot_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)


def test_counter_and_gauge_semantics():
    c = Counter("c")
    c.inc()
    c.inc(41)
    assert c.value == 42
    g = Gauge("g")
    g.set(10)
    g.set(3)
    assert g.value == 3 and g.max == 10  # high-water survives lower sets
    g.set_max(99)
    assert g.value == 3 and g.max == 99


def test_histogram_bucketing_edges():
    h = Histogram("h", bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0):  # upper edges are inclusive
        h.observe(v)
    h.observe(5.0)
    h.observe(10.0)
    h.observe(100.5)  # overflow bucket
    d = h.to_dict()
    assert d["bounds"] == [1.0, 10.0, 100.0]
    assert d["counts"] == [2, 2, 0, 1]
    assert d["count"] == 5
    assert d["min"] == 0.5 and d["max"] == 100.5
    assert d["sum"] == pytest.approx(117.0)


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError):
        Histogram("h", bounds=(10.0, 1.0))


def test_registry_get_or_create_snapshot_reset():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    reg.counter("a").inc(5)
    reg.gauge("b").set(2.5)
    reg.histogram("c", bounds=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["b"] == {"value": 2.5, "max": 2.5}
    assert snap["histograms"]["c"]["counts"] == [1, 0]
    # snapshot is strict-JSON safe (no Infinity literals)
    json.loads(json.dumps(snap))
    reg.reset()
    snap2 = reg.snapshot()
    assert snap2["counters"]["a"] == 0
    assert snap2["gauges"]["b"] == {"value": 0.0, "max": 0.0}
    assert snap2["histograms"]["c"]["count"] == 0
    # instrument identity survives reset (instrumented code holds refs)
    assert reg.counter("a") is reg.counter("a")


def test_counter_thread_safety():
    c = Counter("c")

    def work():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000


def test_metrics_snapshot_thread_safety_fuzz():
    """``metrics_snapshot()`` raced against concurrent counter/gauge/
    histogram mutation AND registry growth from worker threads (the
    scheduler now mutates from part-granular tasks): every snapshot
    must be internally consistent JSON, and the final totals must be
    exact — no lost updates, no dict-mutation crashes."""
    import random

    reg = MetricsRegistry()
    stop = threading.Event()
    errors = []
    done_incs = [0] * 6

    def mutate(i):
        rnd = random.Random(i)
        try:
            while not stop.is_set():
                reg.counter(f"c{rnd.randrange(8)}").inc()
                done_incs[i] += 1
                reg.gauge(f"g{rnd.randrange(4)}").set(rnd.random())
                reg.histogram(
                    f"h{rnd.randrange(4)}", bounds=(0.5,)
                ).observe(rnd.random())
                # registry growth mid-snapshot: fresh names force the
                # name->instrument dicts to mutate under the reader
                reg.counter(f"new.{rnd.randrange(2000)}").inc()
        except Exception as e:  # noqa: BLE001 — the failure under test
            errors.append(e)

    threads = [
        threading.Thread(target=mutate, args=(i,)) for i in range(6)
    ]
    for t in threads:
        t.start()
    snaps = []
    for _ in range(300):
        snap = reg.snapshot()
        snaps.append(snap)
        json.dumps(snap)  # every snapshot is JSON-coherent
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors
    # counter monotonicity across successive snapshots
    prev = -1
    for snap in snaps:
        total = sum(
            v for k, v in snap["counters"].items() if k.startswith("c")
        )
        assert total >= prev
        prev = total
    # exact final totals: no lost updates
    final = reg.snapshot()
    assert sum(
        v for k, v in final["counters"].items()
        if len(k) == 2 and k.startswith("c")
    ) == sum(done_incs)
    for h in (final["histograms"].get(f"h{i}") for i in range(4)):
        if h is not None:
            assert h["count"] == sum(h["counts"])


def test_openmetrics_export_format():
    from torchsnapshot_tpu.obs.export import export_openmetrics

    reg = MetricsRegistry()
    reg.counter("storage.fs.write_bytes").inc(42)
    reg.gauge("budget_bytes_in_use").set(7.5)
    h = reg.histogram("lat", bounds=(1.0, 10.0))
    for v in (0.5, 5.0, 99.0):
        h.observe(v)
    text = export_openmetrics(reg)
    lines = text.splitlines()
    # the TYPE line names the SAMPLE metric (_total included), the
    # classic-format convention node_exporter itself follows
    assert "# TYPE tsnp_storage_fs_write_bytes_total counter" in lines
    assert "tsnp_storage_fs_write_bytes_total 42" in lines
    assert "tsnp_budget_bytes_in_use 7.5" in lines
    assert "tsnp_budget_bytes_in_use_max 7.5" in lines
    # histogram buckets are CUMULATIVE and end with +Inf == count
    assert 'tsnp_lat_bucket{le="1"} 1' in lines
    assert 'tsnp_lat_bucket{le="10"} 2' in lines
    assert 'tsnp_lat_bucket{le="+Inf"} 3' in lines
    assert "tsnp_lat_count 3" in lines
    assert any(ln.startswith("tsnp_lat_sum ") for ln in lines)


def test_metrics_textfile_knob_dumps_on_take(tmp_path):
    from torchsnapshot_tpu import knobs

    target = tmp_path / "metrics.prom"
    with knobs.override_metrics_textfile(str(target)):
        Snapshot.take(
            str(tmp_path / "snap"),
            {"m": StateDict(x=np.arange(1000.0))},
        )
    text = target.read_text()
    assert "tsnp_bytes_written_total" in text
    assert "tsnp_goodput_time_to_unblock_s" in text
    # atomic-write discipline: no temp leftovers next to the target
    assert not [
        p for p in tmp_path.iterdir() if p.name.startswith(".tsnp-metrics-")
    ]


def test_metrics_textfile_off_by_default(tmp_path):
    assert obs.maybe_write_metrics_textfile() is None


def test_metrics_textfile_pid_placeholder(tmp_path):
    """Co-hosted worker processes share the env var: the {pid}
    placeholder keeps their dumps from clobbering one another."""
    import os

    from torchsnapshot_tpu import knobs

    with knobs.override_metrics_textfile(str(tmp_path / "m-{pid}.prom")):
        written = obs.maybe_write_metrics_textfile()
    assert written == str(tmp_path / f"m-{os.getpid()}.prom")
    assert os.path.exists(written)


def test_buf_nbytes_extension_dtypes_and_fallbacks():
    import ml_dtypes

    # bf16 (the primary TPU dtype) rejects memoryview(...).cast("B");
    # a len() fallback would report the first-dim length, not bytes
    arr = np.ones((4, 3), dtype=ml_dtypes.bfloat16)
    assert obs.buf_nbytes(arr) == 24
    assert obs.buf_nbytes(np.zeros(10, np.float64)) == 80
    assert obs.buf_nbytes(b"abc") == 3
    assert obs.buf_nbytes(memoryview(b"abcd")) == 4
    assert obs.buf_nbytes(bytearray(5)) == 5
    assert obs.buf_nbytes(None) == 0


def test_rss_profiler_publishes_peak_gauge():
    from torchsnapshot_tpu.rss_profiler import measure_rss_deltas

    g = obs.gauge(obs.RSS_PEAK_DELTA_BYTES)
    deltas = []
    with measure_rss_deltas(deltas):
        _ = bytearray(8 << 20)  # force some RSS movement
    assert deltas
    assert g.value == max(deltas)


def test_goodput_rollup_shape_and_json_safety(tmp_path):
    from torchsnapshot_tpu.obs import goodput

    goodput.reset()
    try:
        Snapshot.take(
            str(tmp_path / "snap"), {"m": StateDict(x=np.arange(2000.0))}
        )
        block = goodput.block()
        for key in (
            "takes",
            "durable_commits",
            "time_to_unblock_s",
            "durability_lag_s",
            "overhead_fraction",
            "blocked_total_s",
        ):
            assert key in block, key
        assert block["takes"] >= 1
        assert block["durable_commits"] >= 1
        assert block["time_to_unblock_s"] > 0
        json.loads(json.dumps(block))  # flight records are strict JSON
        gauges = obs.metrics_snapshot()["gauges"]
        assert gauges[obs.GOODPUT_TIME_TO_UNBLOCK_S]["value"] > 0
    finally:
        goodput.reset()


def test_take_restore_populate_registry(tmp_path):
    obs.reset_metrics()
    path = str(tmp_path / "snap")
    state = StateDict(x=np.arange(50000.0), n=3)
    Snapshot.take(path, {"m": state})
    out = StateDict(x=np.zeros(50000), n=0)
    Snapshot(path).restore({"m": out})
    snap = obs.metrics_snapshot()
    nbytes = state["x"].nbytes
    assert snap["counters"][obs.BYTES_STAGED] >= nbytes
    assert snap["counters"][obs.BYTES_WRITTEN] >= nbytes
    assert snap["counters"][obs.BYTES_READ] >= nbytes
    assert snap["gauges"][obs.BUDGET_BYTES_IN_USE]["max"] >= nbytes
    # the read pipeline reports through its own gauge (an async_take's
    # background drain can overlap a restore)
    assert snap["gauges"]["budget_bytes_in_use_read"]["max"] >= nbytes
    # per-backend storage latency histograms recorded both directions
    assert snap["histograms"]["storage.fs.write_latency_s"]["count"] > 0
    assert snap["histograms"]["storage.fs.read_latency_s"]["count"] > 0
    assert snap["counters"]["storage.fs.write_bytes"] > 0


def _take_stats_fixture(tmp_path):
    path = str(tmp_path / "snap")
    Snapshot.take(
        path,
        {
            "m": StateDict(
                big=np.arange(100000, dtype=np.float32),
                small=np.ones(10, dtype=np.float64),
                n=5,
                label="hello",
            )
        },
    )
    return path


def test_cli_stats_human_output(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    path = _take_stats_fixture(tmp_path)
    assert main(["stats", path]) == 0
    out = capsys.readouterr().out
    assert "entries" in out
    assert "by dtype:" in out
    assert "float32" in out
    assert "m/big" in out  # largest-entries table names the big leaf
    assert "390.6KB" in out  # 100000 * 4 bytes, human-formatted


def test_cli_stats_json_output(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    path = _take_stats_fixture(tmp_path)
    assert main(["stats", path, "--json", "--top", "2"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["entries"] == 4
    assert stats["total_bytes"] >= 100000 * 4 + 10 * 8
    assert stats["by_dtype"]["float32"]["bytes"] == 100000 * 4
    assert len(stats["largest"]) == 2
    assert stats["largest"][0]["path"].endswith("m/big")
    kinds = set(stats["by_kind"])
    assert any(k in kinds for k in ("Array", "array"))


def test_cli_stats_zero_dim_array_shape(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    path = str(tmp_path / "snap")
    Snapshot.take(
        path,
        {"m": StateDict(scale=np.array(2.5, dtype=np.float32))},
    )
    assert main(["stats", path, "--json"]) == 0
    stats = json.loads(capsys.readouterr().out)
    (entry,) = [e for e in stats["largest"] if e["path"].endswith("scale")]
    assert entry["shape"] == []  # 0-d array, NOT null


def test_cli_stats_missing_snapshot_errors(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    rc = main(["stats", str(tmp_path / "nope")])
    assert rc == 1
    assert "error:" in capsys.readouterr().err


def test_human_formatter_tb_sizes():
    from torchsnapshot_tpu.__main__ import _human

    # the pre-fix fallthrough printed multi-TB sizes as "2048.0B"
    assert _human(2048 * 1024**4) == "2048.0TB"
    assert _human(3 * 1024**4) == "3.0TB"
    assert _human(1536) == "1.5KB"
    assert _human(100) == "100B"


def _take_codec_stats_fixture(tmp_path):
    from torchsnapshot_tpu import codec, knobs

    name = [n for n in codec.available_codecs() if n != "raw"][0]
    rng = np.random.default_rng(0)
    path = str(tmp_path / "codec-snap")
    with knobs.override_codec(name), knobs.override_write_checksums(True):
        Snapshot.take(
            path,
            {
                "m": StateDict(
                    w=(rng.standard_normal(1 << 15) * 0.02).astype(
                        np.float32
                    ),
                )
            },
        )
    return path, name


def test_cli_stats_codec_rollup_json(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    path, name = _take_codec_stats_fixture(tmp_path)
    assert main(["stats", path, "--json"]) == 0
    stats = json.loads(capsys.readouterr().out)
    rollup = stats["codec"]
    assert name in rollup["by_codec"]
    b = rollup["by_codec"][name]
    assert b["objects"] >= 1
    assert 0 < b["stored_bytes"] < b["raw_bytes"]
    assert rollup["ratio"] > 1.0
    assert rollup["raw_bytes"] >= (1 << 15) * 4


def test_cli_stats_codec_rollup_human(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    path, name = _take_codec_stats_fixture(tmp_path)
    assert main(["stats", path]) == 0
    out = capsys.readouterr().out
    assert "codec:" in out
    assert name in out
    assert "x)" in out  # per-codec achieved ratio


def test_cli_stats_codec_rollup_raw_snapshot(tmp_path, capsys):
    """A snapshot with compression off (or pre-codec-era) reports its
    objects under the synthetic "raw" codec with ratio 1."""
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.__main__ import main

    path = str(tmp_path / "raw-snap")
    with knobs.override_codec("raw"), knobs.override_write_checksums(True):
        Snapshot.take(
            path, {"m": StateDict(w=np.arange(1000, dtype=np.float32))}
        )
    assert main(["stats", path, "--json"]) == 0
    stats = json.loads(capsys.readouterr().out)
    rollup = stats["codec"]
    assert set(rollup["by_codec"]) == {"raw"}
    assert rollup["ratio"] == 1.0


# ------------------------------------------------- publication rollups


def _publish_stats_fixture(tmp_path):
    from torchsnapshot_tpu.publish import Publisher, Subscriber

    root = str(tmp_path / "pub")
    w = np.arange(4096, dtype=np.float32)
    pub = Publisher(root, chunk_size_bytes=1024)
    state = {"app": StateDict(w=np.zeros(4096, np.float32))}
    sub = Subscriber(root, state, sub_id="sub-cli")
    try:
        pub.publish_state({"app": StateDict(w=w.copy())}, 1)
        sub.poll_once()
        w[0] = -1.0
        pub.publish_state({"app": StateDict(w=w.copy())}, 2)
        sub.poll_once()
    finally:
        sub.close()
        pub.close()
    return root


def test_cli_stats_publication_root_human(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    root = _publish_stats_fixture(tmp_path)
    assert main(["stats", root]) == 0
    out = capsys.readouterr().out
    assert "[publication root]" in out
    assert "published step 2" in out
    assert "source: state" in out
    # the delta rollup: one 1KB chunk of a 16KB leaf moved
    assert "last update:" in out
    assert "1/16 chunks" in out
    # the fleet lag row from the subscriber's stamp
    assert "sub-cli: step 2 (lag 0 steps" in out


def test_cli_stats_publication_root_json_parity(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    root = _publish_stats_fixture(tmp_path)
    assert main(["stats", root, "--json"]) == 0
    roll = json.loads(capsys.readouterr().out)
    assert roll["step"] == 2
    assert roll["source"] == "state"
    assert roll["stats"]["bytes_delta"] == 1024
    assert roll["stats"]["bytes_total"] == 4096 * 4
    (entry,) = roll["subscribers"]
    assert entry["id"] == "sub-cli"
    assert entry["lag_steps"] == 0
    assert entry["generation"] == 2
    assert entry["bytes_fetched"] >= 4096 * 4  # cold fetch + delta
