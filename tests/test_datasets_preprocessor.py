"""``DataSetPreProcessor`` / ``iterator.set_pre_processor`` (the
reference's hook) on the iterators of ``datasets/``, and the
``BlockDiffusionNoiser`` instance of it."""

import threading

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import (
    ArrayDataSetIterator, DataSet, DataSetIterator, DataSetPreProcessor,
    ListDataSetIterator)
from deeplearning4j_tpu.datasets.diffusion import BlockDiffusionNoiser
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator, EarlyTerminationDataSetIterator,
    MultipleEpochsIterator)
from deeplearning4j_tpu.nn.layers.decoder import IGNORE_LABEL
from deeplearning4j_tpu.observe.registry import MetricsRegistry
from deeplearning4j_tpu.observe.tracer import SpanTracer, thread_tracer


class Scale(DataSetPreProcessor):
    """Features times a factor; says where and as what it was called."""

    def __init__(self, factor=2.0):
        self.factor, self.calls, self.threads = factor, [], set()

    def pre_process(self, batch, epoch=0, index=0):
        self.calls.append((epoch, index))
        self.threads.add(threading.current_thread().name)
        return DataSet(np.asarray(batch.features) * self.factor,
                       batch.labels)


def rows(n=8, width=3):
    x = np.arange(n * width, dtype=np.float32).reshape(n, width)
    return DataSet(x, np.arange(n, dtype=np.float32)[:, None])


def test_an_iterator_without_a_pre_processor_hands_out_its_own_batches():
    it = ArrayDataSetIterator(rows(), 4)
    assert it.pre_processor is None
    assert [b.features.tolist() for b in it] == [
        rows().features[:4].tolist(), rows().features[4:].tolist()]


@pytest.mark.parametrize("make", [
    lambda: ArrayDataSetIterator(rows(), 4),
    lambda: ArrayDataSetIterator(rows(), 4, shuffle=True, seed=3),
    lambda: ListDataSetIterator(list(ArrayDataSetIterator(rows(), 4))),
    lambda: EarlyTerminationDataSetIterator(
        ArrayDataSetIterator(rows(), 2), 2),
    lambda: MultipleEpochsIterator(ArrayDataSetIterator(rows(), 4), 1),
], ids=["array", "shuffled", "list", "early_termination", "multiple_epochs"])
def test_every_iterator_honours_the_hook(make):
    plain = [np.asarray(b.features).copy() for b in make()]
    it = make()
    pp = Scale(3.0)
    it.set_pre_processor(pp)
    assert it.pre_processor is pp
    got = [np.asarray(b.features) for b in it]
    assert len(got) == len(plain) == 2
    for g, p in zip(got, plain):
        np.testing.assert_array_equal(g, 3.0 * p)
    assert pp.calls == [(0, 0), (0, 1)]
    list(it)                                    # the next pass is epoch 1
    assert pp.calls[2:] == [(1, 0), (1, 1)]
    it.set_pre_processor(None)
    assert len(list(it)) == 2 and len(pp.calls) == 4


def test_a_users_own_iterator_honours_it_too():
    class Mine(DataSetIterator):
        def __iter__(self):
            yield rows(2)
            yield rows(2)

    it = Mine()
    it.set_pre_processor(Scale(2.0))
    for batch in it:
        np.testing.assert_array_equal(batch.features, 2 * rows(2).features)


def test_under_the_prefetch_wrapper_it_runs_on_the_worker_inside_produce():
    base = ArrayDataSetIterator(rows(), 2, shuffle=True, seed=1)
    tracer = SpanTracer(enabled=True)
    it = AsyncDataSetIterator(base, tracer=tracer)

    class Spanning(Scale):
        def pre_process(self, batch, epoch=0, index=0):
            assert thread_tracer() is tracer
            with thread_tracer().span("mine", cat="data"):
                return super().pre_process(batch, epoch, index)

    pp = Spanning()
    it.set_pre_processor(pp)                    # handed on to the base
    assert base.pre_processor is pp and it.pre_processor is pp
    got = list(it)
    assert len(got) == 4 and pp.calls == [(0, i) for i in range(4)]
    assert threading.current_thread().name not in pp.threads
    events = tracer.events
    produce = [e for e in events if e["name"] == "produce"]
    mine = [e for e in events if e["name"] == "mine"]
    assert len(produce) == len(mine) == 4
    for outer, inner in zip(produce, mine):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        # the batch handed out is the pre-processor's own allocation: the
        # span says nothing of the gathered batch's memory
        assert "reused" not in outer["args"]
    assert thread_tracer().enabled is False     # this thread names none


def test_the_pooled_buffers_are_used_again_under_a_pre_processor():
    it = ArrayDataSetIterator(rows(64, 8), 8, shuffle=True, seed=2)
    it.set_pre_processor(Scale())
    for _ in range(3):
        seen = [b for b in it]
    # another batch comes back: it says nothing of the gathered one's memory
    assert not any(hasattr(b, "reused_buffers") for b in seen)
    assert len(it._pools[0]) <= 2

    class InPlace(DataSetPreProcessor):
        def pre_process(self, batch, epoch=0, index=0):
            batch.features *= 2.0           # as the reference's: in place
            return batch

    it.set_pre_processor(InPlace())
    del seen                    # a batch that is held keeps its memory
    for _ in range(2):
        said = [b.reused_buffers for b in it]
    assert all(said)


# ---- the noiser ------------------------------------------------------------------

MASK = 99


def clean(n=64, t=256, seed=0):
    return DataSet(np.random.default_rng(seed).integers(
        0, MASK, size=(n, t)).astype(np.int32), None)


def test_the_noiser_makes_noisy_clean_and_the_labels_with_their_weights():
    noiser = BlockDiffusionNoiser(MASK, eps=1e-3, seed=4,
                                  registry=MetricsRegistry())
    batch = clean()
    out = noiser.pre_process(batch, epoch=2, index=5)
    x0 = batch.features
    n, t = x0.shape
    assert out.features.shape == (n, 2 * t) and out.features.dtype == np.int32
    assert out.labels.shape == (n, t, 2) and out.labels.dtype == np.float32
    xt, kept = out.features[:, :t], out.features[:, t:]
    np.testing.assert_array_equal(kept, x0)
    level, masked = noiser.draw(n, t, epoch=2, index=5)
    assert ((1e-3 <= level) & (level <= 1.0)).all()
    np.testing.assert_array_equal(xt == MASK, masked)
    np.testing.assert_array_equal(xt[~masked], x0[~masked])
    ids, weight = out.labels[..., 0], out.labels[..., 1]
    np.testing.assert_array_equal(ids[masked], x0[masked])
    assert (ids[~masked] == IGNORE_LABEL).all() and (weight[~masked] == 0).all()
    want = np.broadcast_to((1.0 / level)[:, None], (n, t))
    np.testing.assert_allclose(weight[masked], want[masked], rtol=1e-6)
    # [MASK] is never a target
    assert not (ids == MASK).any()
    # each row's masked share within four binomial deviations of its level
    share = masked.mean(axis=1)
    assert (np.abs(share - level)
            <= 4 * np.sqrt(level * (1 - level) / t) + 1e-9).all()
    # levels spread over (0, 1): a uniform draw
    assert level.min() < 0.1 and level.max() > 0.9


def test_another_draw_each_epoch_and_the_same_for_the_same_three():
    noiser = BlockDiffusionNoiser(MASK, seed=4, registry=MetricsRegistry())
    a = noiser.pre_process(clean(4, 64), epoch=0, index=1).features
    b = noiser.pre_process(clean(4, 64), epoch=1, index=1).features
    c = noiser.pre_process(clean(4, 64), epoch=0, index=2).features
    again = noiser.pre_process(clean(4, 64), epoch=0, index=1).features
    other = BlockDiffusionNoiser(MASK, seed=5, registry=MetricsRegistry())
    d = other.pre_process(clean(4, 64), epoch=0, index=1).features
    np.testing.assert_array_equal(a, again)
    for x in (b, c, d):
        assert (x != a).any()
    # through an iterator: every pass of the same rows is noised anew
    it = ArrayDataSetIterator(clean(4, 64), 2)
    it.set_pre_processor(noiser)
    first = [x.features for x in it]
    second = [x.features for x in it]
    assert all((p != q).any() for p, q in zip(first, second))
    np.testing.assert_array_equal(first[1][:, 64:], second[1][:, 64:])


def test_the_mask_id_is_no_datum_and_the_rows_are_ids():
    noiser = BlockDiffusionNoiser(MASK, registry=MetricsRegistry())
    bad = clean(2, 16)
    bad.features[1, 3] = MASK
    with pytest.raises(ValueError, match="reserved"):
        noiser.pre_process(bad)
    with pytest.raises(ValueError, match="integer token ids"):
        noiser.pre_process(DataSet(np.zeros((2, 16), np.float32), None))
    with pytest.raises(ValueError, match="eps"):
        BlockDiffusionNoiser(MASK, eps=0.0)
    with pytest.raises(ValueError, match="mask_id"):
        BlockDiffusionNoiser(2 ** 24)


def test_the_gauge_and_the_noise_span():
    reg = MetricsRegistry()
    noiser = BlockDiffusionNoiser(MASK, seed=1, registry=reg)
    tracer = SpanTracer(enabled=True)
    it = AsyncDataSetIterator(ArrayDataSetIterator(clean(6, 32), 2),
                              tracer=tracer)
    it.set_pre_processor(noiser)
    batches = list(it)
    last = batches[-1]
    share = (last.features[:, :32] == MASK).mean()
    assert reg.get_metric("dl4j_diffusion_masked_share").get() == share
    noise = [e for e in tracer.events if e["name"] == "noise"]
    produce = [e for e in tracer.events if e["name"] == "produce"]
    assert len(noise) == len(produce) == 3
    for outer, inner, batch in zip(produce, noise, batches):
        assert inner["cat"] == "data"
        assert inner["args"]["seq"] == 32 and inner["args"]["rows"] == 2
        assert inner["args"]["masked"] == int(
            (batch.features[:, :32] == MASK).sum())
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    # off the prefetch thread and untraced: no span, the same batch
    plain = noiser.pre_process(clean(6, 32), epoch=0, index=0)
    assert len([e for e in tracer.events if e["name"] == "noise"]) == 3
    assert plain.features.shape == (6, 64)


def test_a_features_mask_is_doubled_and_a_labels_mask_passes():
    noiser = BlockDiffusionNoiser(MASK, registry=MetricsRegistry())
    batch = clean(2, 8)
    batch.features_mask = np.array([[1] * 8, [1] * 5 + [0] * 3], np.float32)
    batch.labels_mask = np.ones((2,), np.float32)
    out = noiser.pre_process(batch)
    np.testing.assert_array_equal(
        out.features_mask, np.concatenate([batch.features_mask] * 2, 1))
    assert out.labels_mask is batch.labels_mask
