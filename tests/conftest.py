"""Test config: force an 8-device virtual CPU mesh so sharding semantics are
tested without TPU hardware (SURVEY.md §4: multi-host semantics via CPU
mesh; reference uses torch-elastic multiprocess, test_utils.py:232-270)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _close_resilience_breakers():
    """Circuit breakers are process-global per backend: a test that
    deliberately exhausts retries (chaos schedules) must not leave the
    's3'/'fs' breaker open for every later test in the worker."""
    yield
    from torchsnapshot_tpu.resilience import reset_breakers

    reset_breakers()


@pytest.fixture(params=[True, False], ids=["batching_on", "batching_off"])
def toggle_batching(request):
    """Run snapshot tests with batching on and off (reference
    tests/conftest.py:17-20)."""
    from torchsnapshot_tpu import knobs

    with knobs.override_disable_batching(not request.param):
        yield request.param
