"""``ArrayDataSetIterator``: a shuffled pass hands out the batches of
``DataSet.shuffle(seed + epoch)`` bit for bit without ever holding a
shuffled copy of the set; an unshuffled pass hands out views."""

import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator, DataSet


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


def test_shuffled_batches_are_the_callers_own():
    """No output buffer is reused: a batch kept from a pass (the prefetch
    queue holds eight) is not written by a later one."""
    data = _set(40)
    it = ArrayDataSetIterator(data, 8, shuffle=True)
    kept = list(it)
    copies = [b.features.copy() for b in kept]
    list(it)
    for batch, copy in zip(kept, copies):
        assert not np.shares_memory(batch.features, data.features)
        np.testing.assert_array_equal(batch.features, copy)


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
