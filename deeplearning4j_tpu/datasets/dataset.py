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


class DataSetIterator:
    """Iterator protocol: iterable over DataSet minibatches with reset().
    Matches the reference's interface surface (batch(), totalOutcomes(),
    resetSupported(), asyncSupported()) where meaningful in Python."""

    def __iter__(self) -> Iterator[DataSet]:
        raise NotImplementedError

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


class ArrayDataSetIterator(DataSetIterator):
    """Batches a single large DataSet (analog of creating an iterator from
    arrays; supports shuffling each epoch).

    With ``shuffle`` a pass draws the permutation ``DataSet.shuffle(seed +
    epoch)`` draws and gathers each batch from the set as it is asked for,
    so the batches are those of the shuffled set without a copy of the
    whole set: a pass starts in one batch's time, and under
    ``AsyncDataSetIterator`` the gathers run on the prefetch thread beside
    the steps. Without it the batches are views of the set."""

    def __init__(self, data: DataSet, batch_size: int, shuffle: bool = False,
                 seed: int = 0, drop_last: bool = False):
        self._data = data
        self._bs = batch_size
        self._shuffle = shuffle
        self._seed = seed
        self._epoch = 0
        self._drop_last = drop_last

    def __iter__(self):
        n = self._data.num_examples()
        # to the host once a pass: a jax.Array set would cross per batch
        arrays = [None if a is None else np.asarray(a)  # host-sync-ok: host batching pre-transfer
                  for a in self._data._arrays()]
        perm = None
        if self._shuffle:
            perm = self._data._permutation(self._seed + self._epoch)
            self._epoch += 1
        end = n - (n % self._bs) if self._drop_last else n
        for lo in range(0, end, self._bs):
            rows = (slice(lo, lo + self._bs) if perm is None
                    else perm[lo:lo + self._bs])
            yield DataSet(*(None if a is None else a[rows] for a in arrays))

    @property
    def batch_size(self):
        return self._bs
