"""resnet50-tiny64: the zoo's ResNet-50 on TinyImageNet-shaped data.

The model is the zoo's own (``zoo.models.ResNet50(...).conf()`` into a
``ComputationGraph``, which is what its ``init()`` does); the yardstick
only withholds ``init()`` so that the weights can be made from ``--seed``
in one device program (``yardstick/weights.py``).

The data is made here and not by ``TinyImageNetDataSetIterator``: its
synthetic generator draws float64 normals class by class and normalises
through two more full copies, 63 s for this set on the sandbox's CPU,
every run. These images are class-structured too (one fixed random
pattern per class under uniform noise, so a model can learn them and the
loss must fall), float32, filled in place, and go through the same
``ArrayDataSetIterator(shuffle=True, drop_last=True)`` that the fetcher
wraps.
"""

from __future__ import annotations

import numpy as np


def build(cfg, seed):
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Nesterovs
    from deeplearning4j_tpu.zoo.models import ResNet50
    u = cfg["updater"]
    zoo = ResNet50(num_classes=cfg["num_classes"], height=cfg["image_size"],
                   width=cfg["image_size"], channels=cfg["channels"],
                   seed=seed, compute_dtype=cfg["compute_dtype"],
                   updater=Nesterovs(u["learning_rate"], u["momentum"]),
                   fused_blocks=cfg["fused_blocks"],
                   fused_impl=cfg["fused_impl"], s2d_stem=cfg["s2d_stem"])
    return ComputationGraph(zoo.conf())


def _images(cfg, seed, n):
    """``n`` float32 images in [0, 1] and their classes."""
    rng = np.random.default_rng(seed)
    s, c, k = cfg["image_size"], cfg["channels"], cfg["num_classes"]
    labels = rng.integers(0, k, n)
    patterns = rng.random((k, s, s, c), dtype=np.float32)
    images = np.empty((n, s, s, c), np.float32)
    for lo in range(0, n, 2048):        # in place: no second 1.2 GB array
        part = images[lo:lo + 2048]
        rng.random(out=part, dtype=np.float32)
        part += patterns[labels[lo:lo + 2048]]
        part *= 0.5
    return images, labels


def _dataset(cfg, seed, n):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    images, labels = _images(cfg, seed, n)
    onehot = np.zeros((n, cfg["num_classes"]), np.float32)
    onehot[np.arange(n), labels] = 1.0
    return DataSet(images, onehot)


def train_set(cfg, seed, batch):
    """The in-memory set as the iterator a user hands to ``fit()``."""
    from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
    return ArrayDataSetIterator(_dataset(cfg, seed, cfg["examples"]), batch,
                                shuffle=True, seed=seed, drop_last=True)


def check_batch(cfg, seed, rows):
    """A few examples for the comparison with the plain reference."""
    return _dataset(cfg, seed + 1, rows)


def request_rows(cfg, seed, n):
    """``n`` feature rows for a serving driver to cut requests from."""
    return _images(cfg, seed, n)[0]


def feature_shape(cfg):
    return (cfg["image_size"], cfg["image_size"], cfg["channels"])


def train_flops_per_example(cfg):
    """Floating-point operations one image needs in one optimizer step:
    the published network's multiply-adds (7x7 stem, not the 8x8 the
    space-to-depth form computes; batch norm, ReLU and the pools are not
    counted), two operations each, forward plus twice that backward."""
    size = cfg["image_size"] // cfg["stem_stride"]
    macs = (size * size * cfg["stem_kernel"] ** 2 * cfg["channels"]
            * cfg["stem_filters"])
    size //= 2                                    # 3x3/2 max pool
    cin = cfg["stem_filters"]
    e = cfg["bottleneck_expansion"]
    for si, (f, blocks) in enumerate(zip(cfg["stage_widths"],
                                         cfg["stage_blocks"])):
        for bi in range(blocks):
            if bi == 0 and si > 0:
                size //= 2                        # stride on the first 1x1
            px = size * size
            macs += px * (cin * f + 9 * f * f + f * e * f)
            if bi == 0:
                macs += px * cin * e * f          # projection shortcut
            cin = e * f
    macs += cin * cfg["num_classes"]
    return 3 * 2 * macs
