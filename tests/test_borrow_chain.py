"""Borrow-protocol hardening (VERDICT r4 next #7; reference:
src/ray/core_worker/reference_counter.h:44): chained borrows across 3
processes, middle-process death, and dead-borrower reconciliation — the
no-leak / no-premature-free invariants under process churn."""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_init():
    info = ray_tpu.init(
        num_cpus=6,
        system_config={"borrow_reaper_period_s": 1.0,
                       "borrow_reaper_strikes": 2},
    )
    yield info
    ray_tpu.shutdown()


def _store_object_count(info) -> int:
    from ray_tpu._private.core_worker import get_core_worker

    st = get_core_worker().store.stats()
    return st["num_objects"] if isinstance(st, dict) else st[1]


@ray_tpu.remote
class Holder:
    """Borrower that can hold a ref and forward it onward."""

    def __init__(self):
        self.held = None

    def hold(self, ref_in_list):
        self.held = ref_in_list[0]
        return True

    def forward_to(self, other):
        assert self.held is not None
        return ray_tpu.get(other.hold.remote([self.held]), timeout=60)

    def read(self):
        return int(np.asarray(ray_tpu.get(self.held, timeout=60)).sum())

    def release(self):
        self.held = None
        return True


def test_chained_borrow_survives_middle_death(ray_init):
    """driver(owner) -> B -> C: kill B; C's borrow (registered with the
    owner directly) must keep the object alive and readable."""
    b, c = Holder.remote(), Holder.remote()
    arr = np.ones(512 * 1024, np.uint8)  # big enough to live in shm
    ref = ray_tpu.put(arr)
    assert ray_tpu.get(b.hold.remote([ref]), timeout=60)
    assert ray_tpu.get(b.forward_to.remote(c), timeout=60)
    time.sleep(0.5)  # let C's add_borrow land at the owner
    ray_tpu.kill(b)
    time.sleep(6.0)  # reaper strikes out B's borrows; C's must survive
    # the driver drops ITS ref too: C's borrow alone holds the object now
    del ref
    time.sleep(1.0)
    assert ray_tpu.get(c.read.remote(), timeout=60) == 512 * 1024
    ray_tpu.kill(c)


def test_dead_borrower_borrows_are_reaped(ray_init):
    """A borrower killed WITHOUT releasing must not pin the owner's object
    forever: the liveness reaper drops its borrows and the object frees
    (observable as that object leaving the store; the store's object COUNT
    also moves when the reaper gets to what the test before left pinned)."""
    from ray_tpu._private.core_worker import get_core_worker

    store = get_core_worker().store
    holder = Holder.remote()
    ref = ray_tpu.put(np.ones(1024 * 1024, np.uint8))
    oid = ref.object_id()
    assert ray_tpu.get(holder.hold.remote([ref]), timeout=60)
    time.sleep(0.5)  # let the holder's add_borrow land at the owner
    assert store.contains(oid)
    ray_tpu.kill(holder)  # dies holding the borrow
    del ref  # owner's local count -> 0; only the dead borrow remains
    deadline = time.time() + 90  # strikes x (period + connect retries)
    while time.time() < deadline:
        if not store.contains(oid):
            break
        time.sleep(0.5)
    assert not store.contains(oid), \
        "dead borrower's borrow leaked the object"


def test_release_chain_frees_exactly_once(ray_init):
    """Orderly release by every borrower frees the object; early releases
    by SOME borrowers must not free it while others still hold it."""
    b, c = Holder.remote(), Holder.remote()
    baseline = _store_object_count(ray_init)
    ref = ray_tpu.put(np.ones(1024 * 1024, np.uint8))
    assert ray_tpu.get(b.hold.remote([ref]), timeout=60)
    assert ray_tpu.get(b.forward_to.remote(c), timeout=60)
    time.sleep(0.5)
    assert ray_tpu.get(b.release.remote(), timeout=60)
    time.sleep(1.5)  # B's remove_borrow lands; C still holds
    assert ray_tpu.get(c.read.remote(), timeout=60) == 1024 * 1024
    del ref
    assert ray_tpu.get(c.read.remote(), timeout=60) == 1024 * 1024
    assert ray_tpu.get(c.release.remote(), timeout=60)
    deadline = time.time() + 30
    while time.time() < deadline:
        if _store_object_count(ray_init) <= baseline:
            break
        time.sleep(0.5)
    assert _store_object_count(ray_init) <= baseline, "object never freed"
    ray_tpu.kill(b)
    ray_tpu.kill(c)
