"""DataSet and iterator protocol.

Analog of ND4J's ``DataSet``/``MultiDataSet`` and the reference's
``DataSetIterator`` contract (consumed by MultiLayerNetwork.fit at
deeplearning4j-nn/.../nn/multilayer/MultiLayerNetwork.java:1268).

A DataSet is a minibatch: features, labels, optional masks. Arrays are host
numpy until they hit the jitted train step — the async prefetch iterator
(datasets/iterators.py) overlaps host ETL with device compute, the analog of
the reference's AsyncDataSetIterator thread.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import threading
from typing import Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np


@dataclasses.dataclass
class DataSet:
    features: Union[np.ndarray, "jax.Array"]
    labels: Optional[Union[np.ndarray, "jax.Array"]] = None
    features_mask: Optional[np.ndarray] = None
    labels_mask: Optional[np.ndarray] = None

    def num_examples(self) -> int:
        return int(self.features.shape[0])

    def split_test_and_train(self, n_train: int) -> Tuple["DataSet", "DataSet"]:
        def sl(a, lo, hi):
            return None if a is None else a[lo:hi]
        n = self.num_examples()
        return (DataSet(*(sl(a, 0, n_train) for a in self._arrays())),
                DataSet(*(sl(a, n_train, n) for a in self._arrays())))

    def _arrays(self):
        return (self.features, self.labels, self.features_mask, self.labels_mask)

    def _permutation(self, seed: int) -> np.ndarray:
        """The order ``shuffle(seed)`` puts the examples in."""
        return np.random.default_rng(seed).permutation(self.num_examples())

    def shuffle(self, seed: int = 0) -> "DataSet":
        perm = self._permutation(seed)
        def idx(a):
            # host-sync-ok: host-side shuffle of numpy arrays pre-transfer
            return None if a is None else np.asarray(a)[perm]  # host-sync-ok: host shuffle
        return DataSet(*(idx(a) for a in self._arrays()))

    @staticmethod
    def merge(batches: Sequence["DataSet"]) -> "DataSet":
        def cat(xs):
            xs = [x for x in xs if x is not None]
            return np.concatenate(  # host-sync-ok: host-side batch merge
                [np.asarray(x) for x in xs],  # host-sync-ok: host batch merge
                axis=0) if xs else None
        return DataSet(cat([b.features for b in batches]),
                       cat([b.labels for b in batches]),
                       cat([b.features_mask for b in batches]),
                       cat([b.labels_mask for b in batches]))


@dataclasses.dataclass
class MultiDataSet:
    """Multiple feature/label arrays for ComputationGraph (analog of ND4J
    MultiDataSet)."""
    features: List[np.ndarray]
    labels: List[np.ndarray]
    features_masks: Optional[List[Optional[np.ndarray]]] = None
    labels_masks: Optional[List[Optional[np.ndarray]]] = None

    def num_examples(self) -> int:
        return int(self.features[0].shape[0])


class DataSetPreProcessor:
    """What an iterator does to each minibatch before it hands it out
    (the reference's ``DataSetPreProcessor``, set with
    ``iterator.setPreProcessor``): normalisation, augmentation, or the
    noise a diffusion trainer draws afresh for every batch."""

    def pre_process(self, batch: DataSet, epoch: int = 0,
                    index: int = 0) -> DataSet:
        """The batch to hand out for ``batch``, the ``index``-th of the
        iterator's ``epoch``-th pass since the pre-processor was set:
        ``batch`` itself, changed in place as the reference's is, or
        another."""
        raise NotImplementedError


def _through_pre_processor(iter_method):
    """``__iter__`` whose batches pass through the iterator's
    pre-processor where one is set; the method as it is where none is."""

    @functools.wraps(iter_method)
    def __iter__(self):
        batches = iter_method(self)
        if self._pre_processor is None:
            return batches
        return self._pre_processed(batches)
    return __iter__


class DataSetIterator:
    """Iterator protocol: iterable over DataSet minibatches with reset().
    Matches the reference's interface surface (batch(), totalOutcomes(),
    resetSupported(), asyncSupported(), setPreProcessor()) where
    meaningful in Python.

    ``set_pre_processor`` works on every iterator: a subclass's own
    ``__iter__`` is wrapped when the class is made, so the pre-processor
    runs where the batch is produced (under ``fit()``, on the prefetch
    thread and inside its ``produce`` span). An iterator that only hands
    on another's batches from another thread gives the pre-processor to
    that one (``AsyncDataSetIterator``)."""

    _pre_processor: Optional[DataSetPreProcessor] = None
    _pre_processed_passes = 0

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        own = cls.__dict__.get("__iter__")
        if own is not None:
            cls.__iter__ = _through_pre_processor(own)

    def __iter__(self) -> Iterator[DataSet]:
        raise NotImplementedError

    def set_pre_processor(self, pre_processor: Optional[DataSetPreProcessor]):
        self._pre_processor = pre_processor
        self._pre_processed_passes = 0

    @property
    def pre_processor(self) -> Optional[DataSetPreProcessor]:
        return self._pre_processor

    def _pre_processed(self, batches):
        epoch = self._pre_processed_passes
        self._pre_processed_passes += 1
        index = 0
        for batch in batches:
            # rebound before the yield: nothing here holds the produced
            # batch (``ArrayDataSetIterator`` uses its memory again). What
            # a batch says of its memory (``reused_buffers``) goes on only
            # with the batch itself, changed in place: another batch is
            # the pre-processor's own allocation
            batch = self._pre_processor.pre_process(batch, epoch, index)
            yield batch
            index += 1

    def reset(self):
        pass

    @property
    def batch_size(self) -> Optional[int]:
        return None

    @property
    def async_supported(self) -> bool:
        return True


class ListDataSetIterator(DataSetIterator):
    """In-memory iterator over a list of pre-built minibatches (analog of
    the reference's ListDataSetIterator)."""

    def __init__(self, batches: Sequence[DataSet]):
        self._batches = list(batches)

    def __iter__(self):
        return iter(self._batches)

    def __len__(self):
        return len(self._batches)

    @property
    def batch_size(self):
        return self._batches[0].num_examples() if self._batches else None


def _refcount_of_a_pooled_buffer_nobody_holds() -> int:
    """What ``sys.getrefcount(pool[i])`` reads while the list ``pool`` is
    the object's only holder (the list's reference and the call's own)."""
    pool = [object()]
    return sys.getrefcount(pool[0])


_UNHELD = _refcount_of_a_pooled_buffer_nobody_holds()


class ArrayDataSetIterator(DataSetIterator):
    """Batches a single large DataSet (analog of creating an iterator from
    arrays; supports shuffling each epoch).

    With ``shuffle`` a pass draws the permutation ``DataSet.shuffle(seed +
    epoch)`` draws and gathers each batch from the set as it is asked for,
    so the batches are those of the shuffled set without a copy of the
    whole set: a pass starts in one batch's time, and under
    ``AsyncDataSetIterator`` the gathers run on the prefetch thread beside
    the steps. Without it the batches are views of the set.

    A shuffled batch is the caller's own: nothing the iterator does later
    writes to it while anything refers to it. Its memory is used again
    once nothing does. Per array of the set the iterator keeps up to
    ``_POOL_BUFFERS`` batch-shaped buffers and gathers into one that
    nobody holds: no batch, no view or slice of one, no queue entry, no
    ``jax.Array`` that adopted it, no transfer the runtime still reads it
    for. CPython's reference count of the buffer sees all of these (every
    view's ``.base`` is the buffer; the runtime keeps the array it was
    given until its transfer is done). Where every kept buffer is held it
    allocates, so a consumer that keeps its batches gets fresh arrays, and
    one that drops them gets memory whose pages have been touched before:
    for a 63 MB batch the first touch costs ten times the gather. Each
    gathered batch says which it got in ``reused_buffers``
    (``AsyncDataSetIterator`` puts it on the ``produce`` span). The short
    last batch of a pass without ``drop_last`` is a fresh array."""

    # what one pass can have in flight: ``AsyncDataSetIterator``'s queue
    # of 8, the feeder's 2 staged, one in the producer's hand and one in
    # the feeder's, and the few whose transfer is done but which the
    # runtime lets go of only at its next call
    _POOL_BUFFERS = 16

    def __init__(self, data: DataSet, batch_size: int, shuffle: bool = False,
                 seed: int = 0, drop_last: bool = False):
        self._data = data
        self._bs = batch_size
        self._shuffle = shuffle
        self._seed = seed
        self._epoch = 0
        self._drop_last = drop_last
        # on the iterator, so that the touched pages outlive a fit() call
        self._pools: List[List[np.ndarray]] = [[] for _ in data._arrays()]
        self._pools_lock = threading.Lock()

    def __iter__(self):
        n = self._data.num_examples()
        # to the host once a pass: a jax.Array set would cross per batch
        arrays = [None if a is None else np.asarray(a)  # host-sync-ok: host batching pre-transfer
                  for a in self._data._arrays()]
        end = n - (n % self._bs) if self._drop_last else n
        if not self._shuffle:
            for lo in range(0, end, self._bs):
                yield DataSet(*(None if a is None else a[lo:lo + self._bs]
                                for a in arrays))
            return
        perm = self._data._permutation(self._seed + self._epoch)
        self._epoch += 1
        for lo in range(0, end, self._bs):
            # no local of this frame may hold a buffer across the yield
            yield self._gather(arrays, perm[lo:lo + self._bs])

    def _gather(self, arrays, rows: np.ndarray) -> DataSet:
        """Rows ``rows`` of every array, as ``a[rows]`` gives them."""
        out, reused = [], True
        full = len(rows) == self._bs    # the short last batch is not pooled
        for a, pool in zip(arrays, self._pools):
            if a is None:
                out.append(None)
                continue
            shape = (len(rows),) + a.shape[1:]
            buf, touched = (self._unheld_buffer(pool, shape, a.dtype) if full
                            else (np.empty(shape, a.dtype), False))
            # "clip": under the default "raise" numpy gathers into a fresh
            # temporary and copies it to ``out``; a permutation's rows are
            # in range, so the mode changes no value
            np.take(a, rows, axis=0, out=buf, mode="clip")
            out.append(buf)
            reused = reused and touched
        batch = DataSet(*out)
        batch.reused_buffers = reused
        return batch

    def _unheld_buffer(self, pool: List[np.ndarray], shape, dtype
                       ) -> Tuple[np.ndarray, bool]:
        """A buffer of ``pool`` that only ``pool`` refers to, and True; or,
        where each is held, a new one (kept while the pool has room) and
        False. The lock makes reading the count and taking the buffer one
        step among this iterator's threads; nothing else can take a
        reference to an object that only the pool refers to."""
        with self._pools_lock:
            if pool and (pool[0].shape != shape or pool[0].dtype != dtype):
                pool.clear()            # the set's arrays were replaced
            for i in range(len(pool)):  # by index: a loop variable holds
                if sys.getrefcount(pool[i]) == _UNHELD:
                    return pool[i], True
            buf = np.empty(shape, dtype)
            if len(pool) < self._POOL_BUFFERS:
                pool.append(buf)
            return buf, False

    @property
    def batch_size(self):
        return self._bs
