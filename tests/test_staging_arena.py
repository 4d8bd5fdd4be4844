"""The staging arena: numpy arrays made inside ``allocating()`` take blocks
the arena keeps from one save to the next (staging_arena.py)."""

import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import _csrc, obs
from torchsnapshot_tpu import staging_arena as arena

BIG = 32 << 20  # the arena's floor: what is smaller stays malloc's


def _handler_of(a) -> str:
    try:
        from numpy._core.multiarray import get_handler_name
    except ImportError:
        from numpy.core.multiarray import get_handler_name
    return get_handler_name(a)


@pytest.fixture
def fresh(monkeypatch):
    """The arena empty, with room for 256 MiB, and empty again after."""
    monkeypatch.setattr(arena, "_saves", 0)  # these tests begin saves they never end
    arena.begin_save(0)  # the first call installs it
    if arena._installed() is None:
        pytest.skip("no native library or no numpy allocator hook here")
    arena.begin_save(256 << 20)
    monkeypatch.setattr(arena, "_saves", 1)  # one save is under way in every test
    yield
    arena.begin_save(0)  # also stops the idle timer of a test that ended a save


def _delta(before, after):
    return {k: after[k] - before[k] for k in before}


def test_a_block_freed_is_the_block_of_the_next_request_of_its_size(fresh):
    s0 = arena.stats()
    with arena.allocating():
        a = np.empty(BIG + 4096, np.uint8)
    assert _handler_of(a) == "tsnp_staging_arena"
    a[:] = 7
    address = a.ctypes.data
    del a
    assert _delta(s0, arena.stats()) == {
        "kept_bytes": BIG + 4096, "live_bytes": 0, "reused": 0, "mapped": 1
    }
    with arena.allocating():
        b = np.empty(BIG + 4096, np.uint8)
        other = np.empty(BIG + 8192, np.uint8)  # another size: a mapping of its own
    assert b.ctypes.data == address and int(b[-1]) == 7
    assert other.ctypes.data != address
    assert _delta(s0, arena.stats()) == {
        "kept_bytes": 0, "live_bytes": 2 * BIG + 12288, "reused": 1, "mapped": 2
    }


def test_small_arrays_and_arrays_made_outside_are_not_the_arenas(fresh):
    s0 = arena.stats()
    with arena.allocating():
        small = np.empty(BIG - 1, np.uint8)
    outside = np.empty(BIG, np.uint8)
    assert _handler_of(outside) == "default_allocator"
    del small, outside
    assert _delta(s0, arena.stats()) == dict.fromkeys(s0, 0)


def test_the_hook_is_put_back_even_when_the_body_raises(fresh):
    with pytest.raises(ValueError):
        with arena.allocating():
            raise ValueError("injected")
    assert _handler_of(np.empty(BIG, np.uint8)) == "default_allocator"


def test_another_thread_is_not_inside(fresh):
    seen = {}
    inside = threading.Event()
    leave = threading.Event()

    def other():
        inside.wait(10)
        seen["handler"] = _handler_of(np.empty(BIG, np.uint8))
        leave.set()

    t = threading.Thread(target=other)
    t.start()
    with arena.allocating():
        inside.set()
        assert leave.wait(10)
    t.join()
    assert seen["handler"] == "default_allocator"


def _held():
    st = arena.stats()
    return st["kept_bytes"] + st["live_bytes"]


def test_kept_and_handed_out_together_stay_under_the_cap(fresh):
    """A save whose objects have other sizes than the save before: the
    blocks kept from that one make room, oldest first, as this one maps
    its own, so the two never add up to more than the budget."""
    cap = 4 * BIG
    arena.begin_save(cap)
    base = _held()  # 0 unless another test's array is still alive
    with arena.allocating():
        first = [np.empty(BIG, np.uint8) for _ in range(4)]
    del first
    arena.end_save()
    assert arena.stats()["kept_bytes"] == 4 * BIG
    second = []
    for held_after in (3 * BIG + 4096, 4 * BIG + 8192 - BIG, 3 * BIG + 12288):
        with arena.allocating():
            second.append(np.empty(BIG + 4096, np.uint8))
        assert _held() - base == held_after <= cap
    assert arena.stats()["kept_bytes"] == 0
    del second
    arena.end_save()
    assert _held() - base == 3 * BIG + 12288
    # the first save's size again: its blocks are gone, so a new mapping,
    # for which one of the second save's makes room
    s0 = arena.stats()
    with arena.allocating():
        again = np.empty(BIG, np.uint8)
    assert arena.stats()["mapped"] - s0["mapped"] == 1
    assert _held() - base == 3 * BIG + 8192 <= cap


def test_one_object_larger_than_the_cap_is_mapped_alone_and_not_kept(fresh):
    arena.begin_save(2 * BIG)
    with arena.allocating():
        kept = np.empty(BIG, np.uint8)
    del kept
    assert arena.stats()["kept_bytes"] == BIG
    with arena.allocating():
        large = np.empty(3 * BIG, np.uint8)  # the budget admits such an object alone
    assert arena.stats()["kept_bytes"] == 0
    del large
    assert arena.stats()["kept_bytes"] == 0


def test_the_oldest_kept_block_goes_first(fresh):
    arena.begin_save(3 * BIG)
    with arena.allocating():
        blocks = [np.empty(BIG + 1024 * i, np.uint8) for i in range(2)]
    while blocks:
        blocks.pop(0)  # given back oldest first: sizes BIG, BIG+1024
    s0 = arena.stats()
    with arena.allocating():
        other = np.empty(BIG + 2048, np.uint8)  # room for it and one kept block
    assert arena.stats()["kept_bytes"] == BIG + 1024
    del other
    with arena.allocating():
        newer = np.empty(BIG + 1024, np.uint8)
    assert arena.stats()["reused"] - s0["reused"] == 1
    del newer
    arena.begin_save(0)
    assert arena.stats()["kept_bytes"] == 0


@pytest.mark.parametrize("make", ["zeros", "resize"])
def test_calloc_and_realloc_are_mallocs(fresh, make):
    with arena.allocating():
        a = np.empty(BIG, np.uint8)
    a[:] = 9
    del a
    s0 = arena.stats()
    with arena.allocating():
        if make == "zeros":
            b = np.zeros(BIG, np.uint8)  # numpy's calloc: not the kept block
            assert int(b.max()) == 0
            assert arena.stats() == s0
        else:
            b = np.empty(BIG, np.uint8)  # the kept block
            b[:] = 5
            b.resize(BIG + 4096, refcheck=False)  # numpy's realloc: out to malloc
            assert int(b[:BIG].min()) == 5 and int(b[:BIG].max()) == 5
            assert arena.stats()["live_bytes"] == s0["live_bytes"]
            assert arena.stats()["kept_bytes"] == s0["kept_bytes"]
    del b  # freed through the arena's handler, by malloc's free


def test_what_is_kept_goes_when_no_save_follows(fresh, monkeypatch):
    monkeypatch.setattr(arena, "_IDLE_RELEASE_S", 0.05)
    with arena.allocating():
        a = np.empty(BIG, np.uint8)
    del a
    arena.end_save()
    assert arena.stats()["kept_bytes"] == BIG
    deadline = time.monotonic() + 10
    while arena.stats()["kept_bytes"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert arena.stats()["kept_bytes"] == 0
    assert arena._idle is None


def test_a_save_that_follows_in_time_finds_what_was_kept(fresh, monkeypatch):
    monkeypatch.setattr(arena, "_IDLE_RELEASE_S", 0.2)
    with arena.allocating():
        a = np.empty(BIG, np.uint8)
    address = a.ctypes.data
    del a
    arena.end_save()
    assert arena._saves == 0
    timer = arena._idle
    arena.begin_save(256 << 20)  # the next save begins
    assert arena._idle is None
    timer.join(10)
    assert arena.stats()["kept_bytes"] == BIG
    with arena.allocating():
        assert np.empty(BIG, np.uint8).ctypes.data == address


def test_nothing_is_let_go_while_another_save_is_under_way(fresh, monkeypatch):
    monkeypatch.setattr(arena, "_IDLE_RELEASE_S", 0.05)
    arena.begin_save(256 << 20)  # a second save, beside the fixture's
    with arena.allocating():
        a = np.empty(BIG, np.uint8)
    del a
    arena.end_save()
    assert arena._idle is None
    time.sleep(0.2)
    assert arena.stats()["kept_bytes"] == BIG
    arena.end_save()
    assert arena._idle is not None


def test_an_arena_that_cannot_be_installed_is_reported_once(monkeypatch):
    monkeypatch.setattr(arena, "_tried", False)
    monkeypatch.setattr(arena, "_hook", None)
    monkeypatch.setattr(_csrc, "load", lambda: None)
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    arena.begin_save(1 << 30)
    arena.begin_save(1 << 30)
    arena.end_save()
    assert counter.value == before + 1
    with arena.allocating():
        a = np.empty(BIG, np.uint8)
    assert _handler_of(a) == "default_allocator"


def test_without_the_hook_arrays_are_made_as_before(monkeypatch):
    monkeypatch.setattr(arena, "_tried", True)
    monkeypatch.setattr(arena, "_hook", None)
    with arena.allocating():
        a = np.empty(BIG, np.uint8)
    assert _handler_of(a) == "default_allocator"
    arena.begin_save(1 << 30)
    assert arena.stats() == {"kept_bytes": 0, "live_bytes": 0, "reused": 0, "mapped": 0}


def test_a_block_no_request_took_for_a_whole_save_goes_at_its_end(fresh):
    with arena.allocating():
        used, idle = np.empty(BIG, np.uint8), np.empty(BIG + 4096, np.uint8)
    del used, idle
    arena.end_save()  # both were given back in the save that ends here: both stay
    assert arena.stats()["kept_bytes"] == 2 * BIG + 4096
    with arena.allocating():
        used = np.empty(BIG, np.uint8)
    del used
    arena.end_save()  # ``idle`` lay there all through this one
    assert arena.stats()["kept_bytes"] == BIG
    arena.end_save()
    assert arena.stats()["kept_bytes"] == 0
