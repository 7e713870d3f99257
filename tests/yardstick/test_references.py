"""Each configuration's plain float32 reference against the system it
checks, on seeded random weights, at a preset small enough for the CPU:
ResNet-50 keeps its published depth and widths on 32x32 images, BERT keeps
its block at width 16. float32 compute on both sides, so what is left is
the order of the arithmetic."""

import dataclasses

import jax
import numpy as np
import pytest

from yardstick import cells
from yardstick.weights import init_on_device


def as_tuples(batch):
    if isinstance(batch.features, (list, tuple)):       # MultiDataSet
        return tuple(batch.features), tuple(batch.labels)
    return (batch.features,), (batch.labels,)


TINY = {
    "resnet50-tiny64.fit": {"image_size": 32, "num_classes": 10,
                            "compute_dtype": "float32"},
    "bert-base-ft128.fit": {"hidden_size": 16, "num_hidden_layers": 2,
                            "num_attention_heads": 2,
                            "intermediate_size": 32, "vocab_size": 40,
                            "max_position_embeddings": 16, "seq_len": 12,
                            "compute_dtype": "float32",
                            "name": "bert-tiny-test"},
}
# float32 against float32-highest: BERT's difference is rounding alone; the
# ResNet's Gram-matrix batch statistics (sum of squares minus squared mean)
# cancel in float32 and fifty normalisations carry that to the loss
TOLERANCE = {"resnet50-tiny64.fit": 1e-3, "bert-base-ft128.fit": 1e-5}


@pytest.fixture(scope="module", params=sorted(TINY))
def system_and_reference(request, tmp_path_factory):
    cell = cells.resolve_cell(request.param)
    cell = dataclasses.replace(cell, config={**cell.config,
                                             **TINY[request.param]})
    build = cells.load_build(cell)
    if hasattr(build, "CACHE"):         # the graph cache stays out of the repo
        build.CACHE = tmp_path_factory.mktemp("graph_cache")
    model = init_on_device(build.build(cell.config, 5), 5)
    return (request.param, cell, build, model, cells.load_reference(cell))


def test_reference_loss_agrees_with_the_system(system_and_reference):
    name, cell, build, model, reference = system_and_reference
    batch = build.check_batch(cell.config, 5, 16)
    feats, labels = as_tuples(batch)
    ts = model.train_state
    want = float(reference.loss(cell.config, ts.params, ts.model_state,
                                feats, labels))
    got = float(model.score(batch))
    assert np.isfinite(want) and abs(got - want) / want < TOLERANCE[name]


def test_reference_is_sensitive_to_every_block(system_and_reference):
    """A reference that skipped a block would still agree with itself:
    negate one weight deep in the network (a scaling would vanish in the
    batch-norm after it) and both must move, and move alike."""
    name, cell, build, model, reference = system_and_reference
    batch = build.check_batch(cell.config, 5, 16)
    feats, labels = as_tuples(batch)
    ts = model.train_state
    deep = {"resnet50-tiny64.fit": ("s3b2", "W2"),
            "bert-base-ft128.fit": ("l1_ff2", "W")}[name]
    bumped = jax.tree_util.tree_map(lambda a: a, ts.params)
    bumped[deep[0]] = dict(bumped[deep[0]])
    bumped[deep[0]][deep[1]] = -bumped[deep[0]][deep[1]]
    before = float(reference.loss(cell.config, ts.params, ts.model_state,
                                  feats, labels))
    after = float(reference.loss(cell.config, bumped, ts.model_state,
                                 feats, labels))
    model.train_state = ts._replace(params=bumped)
    got = float(model.score(batch))
    model.train_state = ts
    assert abs(after - before) > 1e-4
    assert abs(got - after) / after < TOLERANCE[name]


def test_resnet_reference_serves_with_statistics_it_calibrated():
    cell = cells.resolve_cell("resnet50-tiny64.fit")
    cell = dataclasses.replace(
        cell, config={**cell.config, **TINY["resnet50-tiny64.fit"]})
    build, reference = cells.load_build(cell), cells.load_reference(cell)
    model = init_on_device(build.build(cell.config, 6), 6)
    rows = build.request_rows(cell.config, 6, 24)
    ts = model.train_state
    state = reference.batch_statistics(cell.config, ts.params,
                                       ts.model_state, (rows,))
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(ts.model_state))
    model.train_state = ts._replace(model_state=state)
    want = np.asarray(reference.predict(cell.config, ts.params, state,
                                        (rows[:5],)))
    got = np.asarray(model.output(rows[:5]))
    assert got.shape == want.shape == (5, 10)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-3
    # with calibrated statistics the softmax is not saturated
    assert want.max() < 0.999


@pytest.mark.parametrize("name, flops", [
    ("resnet50-tiny64.fit", 1.89e9),       # ~3.86 GMAC at 224, by (64/224)^2
    ("bert-base-ft128.fit", 128 * 0.524e9)])
def test_model_flops_from_shapes(name, flops):
    cell = cells.resolve_cell(name)
    got = cells.load_build(cell).train_flops_per_example(cell.config)
    assert got == pytest.approx(flops, rel=0.01)
