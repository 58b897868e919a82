import os

import pytest

from ray_tpu.native.store import ObjectExistsError, ShmClient, ShmStore, StoreFullError


@pytest.fixture
def store(tmp_path):
    path = "/dev/shm/raytpu_test_" + os.urandom(4).hex()
    s = ShmStore(path, 1 << 20)
    yield s
    s.close()


def oid(i: int) -> bytes:
    return i.to_bytes(28, "little")


def test_create_seal_get(store):
    off = store.create(oid(1), 128, 8)
    store.write(off, b"d" * 128)
    store.write(off + 128, b"m" * 8)
    store.seal(oid(1))
    store.release(oid(1))
    info = store.get_info(oid(1))
    assert info is not None
    offset, dsz, msz = info
    assert (dsz, msz) == (128, 8)
    assert bytes(store.read(offset, 128)) == b"d" * 128


def test_unsealed_not_gettable(store):
    store.create(oid(2), 64, 0)
    assert store.get_info(oid(2)) is None
    assert store.contains(oid(2)) == 1


def test_duplicate_create(store):
    store.create(oid(3), 64, 0)
    with pytest.raises(ObjectExistsError):
        store.create(oid(3), 64, 0)


def test_lru_eviction(store):
    # Fill beyond capacity; sealed refcount-0 objects must be evicted.
    for i in range(40):
        store.put_sealed(oid(100 + i), b"z" * (40 * 1024))
    assert store.used() <= 1 << 20
    assert store.num_objects() < 40
    # Most recent object survives.
    assert store.contains(oid(139)) == 2


def test_pinned_objects_not_evicted(store):
    store.put_sealed(oid(4), b"a" * (200 * 1024))
    store.add_ref(oid(4))  # pin
    for i in range(40):
        store.put_sealed(oid(200 + i), b"z" * (40 * 1024))
    assert store.contains(oid(4)) == 2


def test_store_full_when_all_pinned(store):
    store.create(oid(5), 900 * 1024, 0)  # unsealed = pinned by creator
    with pytest.raises(StoreFullError):
        store.create(oid(6), 900 * 1024, 0)


def test_delete_and_reuse(store):
    off1 = store.create(oid(7), 1024, 0)
    store.seal(oid(7))
    store.release(oid(7))
    assert store.delete(oid(7))
    assert store.contains(oid(7)) == 0
    off2 = store.create(oid(8), 1024, 0)
    assert off2 == off1  # space reused (best-fit allocator)


def test_cross_process_view(store):
    data = os.urandom(4096)
    store.put_sealed(oid(9), data)
    client = ShmClient(store.path, store.capacity)
    offset, dsz, _ = store.get_info(oid(9))
    assert bytes(client.read(offset, dsz)) == data
    client.close()


def test_a_call_after_close_raises_and_does_not_crash(tmp_path):
    """A raylet that was stopped still runs the tails of its coroutines
    (a push's ``finally`` releases its object): on a closed store that is
    a Python exception, never a NULL handle handed to the native code
    (which took the whole pytest worker down with a segmentation fault)."""
    from ray_tpu.native.store import ShmStoreError

    s = ShmStore(str(tmp_path / "closed_store"), 1 << 20)
    oid = b"x" * 20
    s.put_sealed(oid, b"payload")
    s.add_ref(oid)
    s.close()
    for call in (lambda: s.release(oid), lambda: s.add_ref(oid),
                 lambda: s.unpin(oid), lambda: s.contains(oid),
                 lambda: s.ref_count(oid), lambda: s.used(),
                 lambda: s.create(b"y" * 20, 8), lambda: s.evict(1)):
        with pytest.raises(ShmStoreError, match="closed"):
            call()
    s.close()  # idempotent


def test_a_live_segment_is_not_a_starting_sessions_to_clear(store):
    """``conftest.pytest_sessionstart`` clears leaked ``/dev/shm/raytpu_*``
    segments, and only those no live process maps: a store that is open
    (another run's cluster, this one's) is seen as mapped, a closed one
    is not."""
    from conftest import _mapped_shm_paths

    assert store.path in _mapped_shm_paths()
    store.close()
    assert store.path not in _mapped_shm_paths()
