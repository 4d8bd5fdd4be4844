"""chip_smoke.py is the chip check (the builder sends it through the chip
tool); tier-1 runs the same file at ``--tiny`` on the CPU so the script
itself cannot rot, and pins the rule that without ``--tiny`` a CPU run
fails instead of printing a result.  Also the compile-cache helper every
chip-touching program calls."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _env(**extra):
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_tiny_smoke_passes_on_cpu_and_says_so(tmp_path):
    proc = subprocess.run(
        [sys.executable, _SMOKE, "--tiny"],
        env=_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
        ),
        capture_output=True,
        text=True,
        timeout=300,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    # the last line is the verdict, exactly these keys, stamped cpu
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    # every line before it names the platform it ran on; none is chip-shaped
    assert all(ln["platform"] == "cpu" for ln in lines[:-1])
    result = lines[-2]
    assert result["report"] == "chip_smoke"
    assert result["ok"] is True and result["tiny"] is True
    assert result["failures"] == []
    ev = result["evidence"]
    assert ev["loss_a"] == ev["loss_b"]
    assert ev["offload"]["device_offload_bytes"] == ev["state_array_bytes"]
    assert ev["pack_calls"] > 0
    assert ev["exceptions_swallowed"] == 0
    assert ev["native_io"]["library_loaded"] is True
    # four virtual devices: the 2x2 -> 1x4 leg ran too
    assert result["sharded_leg"] == "ran"
    assert ev["sharded"]["tp_sharded_leaves"] > 0
    # the chip-only checks are reported, not silently dropped
    assert {e["check"] for e in result["not_enforced_on_cpu"]} >= {
        "restore donated its templates",
        "flash kernels compiled, not interpreted",
    }
    # compiled entries went where the variable said, and nowhere in code
    assert result["compile_cache_dir"] == str(tmp_path / "cc")
    assert os.listdir(tmp_path / "cc")


def test_without_tiny_a_cpu_run_fails_and_names_jax_platforms(tmp_path):
    proc = subprocess.run(
        [sys.executable, _SMOKE],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc")),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "JAX_PLATFORMS='cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line, not even a failed one


_CACHE_PROBE = (
    "import sys; sys.path.insert(0, {repo!r}); import jax; "
    "from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache; "
    "d = enable_compile_cache(); "
    "print(d); print(jax.config.jax_compilation_cache_dir); "
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
).format(repo=_REPO)


def _probe_cache(env):
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()
    return out


def test_compile_cache_env_dir_is_not_overridden(tmp_path):
    want = str(tmp_path / "from_env")
    used, configured, min_secs = _probe_cache(
        _env(JAX_COMPILATION_CACHE_DIR=want)
    )
    # JAX read the variable itself; the helper set no directory in code
    assert used == want and configured == want
    assert float(min_secs) == 0.0


def test_compile_cache_default_is_fixed_in_checkout_path(tmp_path):
    first = _probe_cache(_env())
    second = _probe_cache(_env(TMPDIR=str(tmp_path)))  # another process
    assert first == second
    assert first[0] == first[1] == os.path.join(_REPO, ".jax_cache")


def test_importing_the_library_sets_no_cache_dir():
    out = subprocess.run(
        [
            sys.executable, "-c",
            f"import sys; sys.path.insert(0, {_REPO!r}); import jax; "
            "import torchsnapshot_tpu, torchsnapshot_tpu.utils.compile_cache; "
            "print(jax.config.jax_compilation_cache_dir)",
        ],
        env=_env(), capture_output=True, text=True, timeout=120, check=True,
    ).stdout.strip()
    assert out == "None"
