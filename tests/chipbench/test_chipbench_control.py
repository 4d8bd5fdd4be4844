"""The comparison that decides ``correct``, shown to fail: the control (the
program's own lossy path, bfloat16 for float32) and the faults a cell can
have, planted underneath a whole run at tiny widths."""

import numpy as np
import pytest

from chipbench import state

RESTORE_CELLS = ["ouro-2.6b-d9.kill_resume", "ouro-2.6b-d32.reshard_resume"]
SAVE_CELLS = ["ouro-2.6b-d9.preempt_sync_save", "ouro-2.6b-d4.async_save_train"]


@pytest.mark.parametrize("workload", RESTORE_CELLS + SAVE_CELLS)
def test_the_bf16_control_comes_out_as_not_correct(benchmark_json, run_tiny, workload):
    result = run_tiny(workload, fault="control_bf16")
    assert result["correct"] is False and result["failed"] == 1
    assert result["checks"]["leaves_mismatched"]["value"] > 0
    assert result["checks"]["leaves_misplaced"]["value"] == 0


@pytest.mark.parametrize("workload", RESTORE_CELLS + SAVE_CELLS)
def test_an_answer_altered_where_it_is_produced_is_caught(benchmark_json, run_tiny, workload):
    result = run_tiny(workload, fault="answer_altered")
    assert result["correct"] is False
    assert result["checks"]["leaves_mismatched"]["value"] >= 1


@pytest.mark.parametrize("workload", SAVE_CELLS)
def test_a_snapshot_of_a_later_state_is_caught(benchmark_json, run_tiny, workload):
    result = run_tiny(workload, fault="late_snapshot")
    assert result["correct"] is False
    assert result["checks"]["leaves_mismatched"]["value"] > 0


def test_the_async_control_also_breaks_the_loss_that_continues(benchmark_json, run_tiny):
    result = run_tiny(SAVE_CELLS[1], fault="control_bf16")
    assert result["checks"]["loss_gap"]["value"] > 0


# ---------------------------------------------------------------- digests


def test_digest_sees_one_changed_word_and_two_swapped_words():
    import jax.numpy as jnp

    digest = state.Digester()
    x = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)
    base = digest([x])
    assert np.array_equal(base, digest([x + 0]))
    assert not np.array_equal(base, digest([x.at[3, 5].add(1)]))
    swapped = x.at[0, 1].set(x[0, 2]).at[0, 2].set(x[0, 1])
    assert base[0, 0] == digest([swapped])[0, 0]  # the plain sum cannot see a swap
    assert base[0, 1] != digest([swapped])[0, 1]  # the weighted sum does


def test_digest_is_the_same_under_every_layout():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    digest = state.Digester()
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    mesh22 = state.build_mesh(jax.devices(), 2, 2)
    mesh14 = state.build_mesh(jax.devices(), 1, 4)
    a = digest([jax.device_put(x, NamedSharding(mesh22, P(None, "tp")))])
    b = digest([jax.device_put(x, NamedSharding(mesh14, P("tp", None)))])
    assert np.array_equal(a, digest([x])) and np.array_equal(a, b)


def test_digest_reads_two_byte_leaves_and_refuses_others():
    import jax.numpy as jnp

    digest = state.Digester()
    x = jnp.linspace(0, 1, 256, dtype=jnp.bfloat16)
    assert not np.array_equal(digest([x]), digest([x.at[7].set(0.5)]))
    with pytest.raises(ValueError):
        digest([jnp.zeros(4, jnp.int8)])


def test_compare_counts_bytes_and_placement_apart():
    ref = np.array([[1, 2], [3, 4]], dtype=np.uint32)
    layout = [((2,), "float32", "s0"), ((3,), "float32", "s0")]
    assert state.compare(ref, ref.copy(), layout, list(layout)) == {
        "leaves_mismatched": 0, "leaves_misplaced": 0}
    got = ref.copy()
    got[1, 1] = 9
    moved = [layout[0], ((3,), "float32", "s1")]
    assert state.compare(ref, got, layout, moved) == {
        "leaves_mismatched": 1, "leaves_misplaced": 1}
    assert state.compare(ref, None, layout, None) == {
        "leaves_mismatched": 2, "leaves_misplaced": 2}
