"""``ArrayDataSetIterator``: a shuffled pass hands out the batches of
``DataSet.shuffle(seed + epoch)`` bit for bit without ever holding a
shuffled copy of the set, gathered into memory it has used before and
that nobody holds any more; an unshuffled pass hands out views."""

import collections
import sys
import threading
import time
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator


def _set(n, masks=False, on_device=False, seed=0):
    rng = np.random.default_rng(seed)
    arrays = [rng.normal(size=(n, 4, 5)).astype(np.float32),
              np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]]
    if masks:
        arrays += [(rng.random((n, 4)) < 0.7).astype(np.float32),
                   (rng.random((n, 1)) < 0.9).astype(np.float32)]
    if on_device:
        arrays = [jnp.asarray(a) for a in arrays]
    return DataSet(*arrays)


def _slices(data, batch, drop_last):
    n = data.num_examples()
    end = n - n % batch if drop_last else n
    return [DataSet(*(None if a is None else np.asarray(a)[lo:lo + batch]
                      for a in data._arrays()))
            for lo in range(0, end, batch)]


def _assert_same_batches(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g._arrays(), w._arrays()):
            if b is None:
                assert a is None
            else:
                assert isinstance(a, np.ndarray) and a.dtype == b.dtype
                np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("on_device", [False, True], ids=["numpy", "jax"])
@pytest.mark.parametrize("seed", [0, 11])
@pytest.mark.parametrize("masks", [False, True], ids=["nomask", "masks"])
@pytest.mark.parametrize("n, drop_last", [(32, False), (37, False),
                                          (37, True), (5, False)])
def test_shuffled_batches_are_slices_of_the_shuffled_set(
        n, drop_last, masks, seed, on_device):
    data = _set(n, masks=masks, on_device=on_device)
    it = ArrayDataSetIterator(data, 8, shuffle=True, seed=seed,
                              drop_last=drop_last)
    for epoch in range(3):
        want = _slices(data.shuffle(seed + epoch), 8, drop_last)
        assert [b.num_examples() for b in want] == (
            [8] * (n // 8) + ([n % 8] if n % 8 and not drop_last else []))
        _assert_same_batches(list(it), want)


@pytest.mark.parametrize("n, drop_last", [(32, False), (37, False),
                                          (37, True)])
def test_unshuffled_batches_are_views_of_the_set(n, drop_last):
    data = _set(n, masks=True)
    got = list(ArrayDataSetIterator(data, 8, drop_last=drop_last))
    _assert_same_batches(got, _slices(data, 8, drop_last))
    for batch in got:
        for a, whole in zip(batch._arrays(), data._arrays()):
            assert a.base is whole


def _hold_batch(batch):
    return batch


def _hold_slice(batch):
    return batch.features[2:5]


def _hold_asarray(batch):
    return np.asarray(batch.labels)


def _hold_jax_array(batch):
    # the CPU backend adopts a numpy array it is given where it can
    return jnp.asarray(batch.features)


_HOLDERS = {"batch": (_hold_batch, lambda w: w),
            "slice": (_hold_slice, lambda w: w.features[2:5]),
            "asarray": (_hold_asarray, lambda w: w.labels),
            "jax_array": (_hold_jax_array, lambda w: w.features)}


def _assert_held(held, want):
    if isinstance(held, DataSet):
        _assert_same_batches([held], [want])
    else:
        np.testing.assert_array_equal(np.asarray(held), want)


@pytest.mark.parametrize("holder", sorted(_HOLDERS))
def test_shuffled_batches_are_the_callers_own(holder):
    """The iterator gathers into buffers it has used before, and only into
    one that nothing refers to: whatever a caller keeps of a batch (the
    batch, a slice of one of its arrays, ``np.asarray`` of one, a
    ``jax.Array`` made from one) is never written by a later batch, over
    passes in which everything else is dropped and comes back."""
    hold, part_of = _HOLDERS[holder]
    data = _set(88)
    it = ArrayDataSetIterator(data, 8, shuffle=True, seed=2)
    kept, reused = [], 0
    for epoch in range(3):
        want = _slices(data.shuffle(2 + epoch), 8, False)
        for i, batch in enumerate(it):
            assert not np.shares_memory(batch.features, data.features)
            reused += batch.reused_buffers
            if i % 3 == 0:
                kept.append((hold(batch), part_of(want[i])))
            del batch
            for held, wanted in kept:
                _assert_held(held, wanted)
    assert len(kept) == 12
    assert reused >= 15                 # of 33: the others' memory came back


def test_a_queued_batch_is_held():
    """``AsyncDataSetIterator``'s queue fills while its consumer dawdles;
    no entry of it is written before it is taken."""
    data = _set(160)
    it = AsyncDataSetIterator(ArrayDataSetIterator(data, 8, shuffle=True),
                              queue_size=8)
    for epoch in range(3):
        want = _slices(data.shuffle(epoch), 8, False)
        got = []
        for i, batch in enumerate(it):
            if i in (0, 9):
                time.sleep(0.05)        # the worker runs the queue full
            got.append(DataSet(*(None if a is None else a.copy()
                                 for a in batch._arrays())))
        _assert_same_batches(got, want)


def test_dropped_batches_come_back():
    """A consumer that drops each batch as it takes the next is handed two
    buffers in turn: batch i + 2 lies where batch i lay, and from the
    third on each says that its memory had been used before."""
    data = _set(80, masks=True)
    it = ArrayDataSetIterator(data, 8, shuffle=True)
    where, reused = [], []
    for _ in range(2):
        for batch in it:
            where.append([a.ctypes.data for a in batch._arrays()])
            reused.append(batch.reused_buffers)
    assert len(where) == 20
    assert where[2:] == where[:-2]
    assert where[0] != where[1]
    assert reused == [False, False] + [True] * 18
    assert [len(pool) for pool in it._pools] == [2, 2, 2, 2]


def test_only_gathered_batches_say_where_they_went():
    data = _set(20)
    assert [b.reused_buffers for b in
            ArrayDataSetIterator(data, 8, shuffle=True)] == [
                False, False, False]    # two held in turn, the short last
    assert not any(hasattr(b, "reused_buffers")
                   for b in ArrayDataSetIterator(data, 8))


def test_threads_sharing_an_iterator_never_share_a_buffer():
    """Sixteen threads pass over one iterator at once, switching every 10
    microseconds: a batch in one thread's hand is never written by another
    thread's gather (taking a buffer is one step under the pool's lock)."""
    data = _set(64)
    it = ArrayDataSetIterator(data, 8, shuffle=True)
    deadline = time.monotonic() + 1.5
    torn, passes = [], []

    def consumer():
        done = 0
        while time.monotonic() < deadline and not torn:
            for batch in it:
                before = batch.features.copy()
                time.sleep(0)           # let the others gather
                if not np.array_equal(batch.features, before):
                    torn.append(before)
            done += 1
        passes.append(done)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=consumer) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not torn
    assert len(passes) == 16 and min(passes) >= 1
    assert len(it._pools[0]) <= it._POOL_BUFFERS


def test_pool_stays_under_its_bound():
    """A consumer that holds its last 20 batches: the iterator keeps no
    more buffers than its bound, allocates for the rest, and writes to
    nothing that is held."""
    data = _set(240)
    it = ArrayDataSetIterator(data, 8, shuffle=True, seed=4)
    held = collections.deque(maxlen=20)
    fresh = 0
    for epoch in range(3):
        want = _slices(data.shuffle(4 + epoch), 8, False)
        for i, batch in enumerate(it):
            held.append((batch, want[i]))
            fresh += not batch.reused_buffers
            del batch
            assert all(len(pool) <= it._POOL_BUFFERS for pool in it._pools)
        _assert_same_batches([b for b, _ in held], [w for _, w in held])
    assert [len(pool) for pool in it._pools[:2]] == [it._POOL_BUFFERS] * 2
    assert 20 <= fresh < 90             # some of the 90 went into used memory


@pytest.mark.parametrize("shuffle", [False, True])
def test_set_goes_to_the_host_once_a_pass(shuffle):
    """A set that is not host numpy (a ``jax.Array``) is converted when a
    pass starts, not once per batch."""
    class Counted:
        def __init__(self, a):
            self.a, self.shape, self.conversions = a, a.shape, 0

        def __array__(self, dtype=None, copy=None):
            self.conversions += 1
            return self.a

    plain = _set(40)
    data = DataSet(Counted(plain.features), Counted(plain.labels))
    it = ArrayDataSetIterator(data, 8, shuffle=shuffle)
    for passes in (1, 2):
        assert len(list(it)) == 5
        assert data.features.conversions == passes
        assert data.labels.conversions == passes


def test_a_shuffled_pass_holds_no_copy_of_the_set():
    """Round one pass over 40 batches the peak of traced memory stays
    under three batches' bytes (the whole-set copy was forty)."""
    rows, batches = 64, 40
    data = DataSet(np.ones((rows * batches, 256), np.float32),
                   np.ones((rows * batches, 16), np.float32))
    batch_bytes = rows * (256 + 16) * 4
    it = ArrayDataSetIterator(data, rows, shuffle=True, seed=3)
    tracemalloc.start()
    try:
        seen = 0
        for batch in it:
            seen += batch.num_examples()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert seen == rows * batches
    assert peak < 3 * batch_bytes
