"""Contract: every BENCH record embeds the goodput block
(bench._goodput_rollup — time-to-unblock, durability lag, overhead
fraction), so the benchmark trajectory carries what each headline
number COST the training loop."""

import ast
import importlib.util
import json
import os

import numpy as np

_BENCH_PATH = os.path.join(
    os.path.dirname(__file__), "..", "bench.py"
)


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", _BENCH_PATH
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_goodput_rollup_shape_and_json_safety(tmp_path):
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.obs import goodput

    goodput.reset()
    try:
        Snapshot.take(
            str(tmp_path / "snap"), {"m": StateDict(x=np.arange(2000.0))}
        )
        bench = _load_bench()
        block = bench._goodput_rollup()
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
        json.loads(json.dumps(block))  # BENCH records are strict JSON
    finally:
        goodput.reset()


def test_every_bench_record_site_embeds_goodput():
    """Static contract over bench.py: the ``result`` record embeds the
    goodput block (the record accumulates, so one assignment before
    the first full-record print covers every later print of it)."""
    with open(_BENCH_PATH) as f:
        src = f.read()
    tree = ast.parse(src)

    # result["goodput"] is assigned in main
    main = next(
        n for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef) and n.name == "main"
    )
    assigned = {
        t.slice.value
        for n in ast.walk(main)
        if isinstance(n, ast.Assign)
        for t in n.targets
        if isinstance(t, ast.Subscript)
        and isinstance(t.value, ast.Name)
        and t.value.id == "result"
        and isinstance(t.slice, ast.Constant)
    }
    assert "goodput" in assigned
    assert "metrics" in assigned  # the record-assembly site it rides


def test_bench_on_cpu_exits_nonzero_without_a_metric_line():
    """bench.py measures the chip or nothing: on the CPU it exits nonzero
    before printing any line under a device metric's name."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, _BENCH_PATH],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "JAX_PLATFORMS='cpu'" in proc.stderr
    assert '"metric"' not in proc.stdout


def test_bench_is_one_process():
    """No supervisor, no ``--child`` re-exec, no module-level subprocess
    import: the only children left are the takeover probe's, and they
    are pinned to the CPU so they can never ask for the chip."""
    with open(_BENCH_PATH) as f:
        src = f.read()
    tree = ast.parse(src)
    top_imports = {
        a.name
        for n in tree.body
        if isinstance(n, ast.Import)
        for a in n.names
    }
    assert "subprocess" not in top_imports
    assert "--child" not in src
    spawners = {
        fn.name
        for fn in tree.body  # top level: nested helpers count as theirs
        if isinstance(fn, ast.FunctionDef)
        and any(
            isinstance(n, ast.Attribute) and n.attr == "Popen"
            for n in ast.walk(fn)
        )
    }
    assert spawners <= {"_takeover_probe"}
    probe = next(
        fn for fn in ast.walk(tree)
        if isinstance(fn, ast.FunctionDef) and fn.name == "_takeover_probe"
    )
    assert '"JAX_PLATFORMS": "cpu"' in ast.get_source_segment(src, probe)
