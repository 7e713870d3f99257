"""phi4-mini-flash-vp8 at a preset small enough for the CPU (hidden 32,
the published pattern at 8 layers of which 0, 1, 4, 5, 6, 7 are built,
window 5, 4 states a channel, T = 24, vocabulary 97, float32): the system
against the plain reference, the edges the configuration shares layers
over, the tied matrix and the vocabulary slice."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yardstick import cells
from yardstick.weights import init_on_device

CELL = "phi4-mini-flash-vp8.fit-seq8k"
TINY = {"hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 5, "mamba_d_state": 4,
        "mamba_dt_rank": 3, "vocab_size": 97,
        "published": {"num_hidden_layers": 8, "vocab_size": 776},
        "layer_indices": [0, 1, 4, 5, 6, 7], "seq_len": 24,
        "examples": 8, "repeated_span": 4,
        "compute_dtype": "float32",
        "updater": {"type": "Adam", "learning_rate": 1e-2}}


def perturb(model, seed, scale=0.05):
    """Biases, lambda vectors and norms start at values that hide a wrong
    use of them (0, 1): move every leaf."""
    leaves, tree = jax.tree_util.tree_flatten(model.train_state.params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    model.set_params(jax.tree_util.tree_unflatten(tree, [
        leaf + scale * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)]))
    return model


@pytest.fixture(scope="module")
def tiny():
    cell = cells.resolve_cell(CELL)
    cell = dataclasses.replace(cell, config={**cell.config, **TINY})
    build = cells.load_build(cell)
    model = perturb(init_on_device(build.build(cell.config, 5), 5), 11)
    return cell.config, build, model, cells.load_reference(cell)


@pytest.fixture(scope="module")
def gradients(tiny):
    """Both gradients on two rows, taken once."""
    cfg, build, model, reference = tiny
    batch = build.check_batch(cfg, 6, 2)
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    params = model.train_state.params
    return (ids, labels,
            jax.jit(jax.grad(system_loss(model)))(params, ids, labels),
            jax.jit(jax.grad(reference.loss_fn(cfg)))(params, ids, labels))


def system_loss(model):
    ts = model.train_state

    def fn(params, ids, labels):
        return model._loss(params, ts.model_state, (ids,), (labels,), None,
                           None, None, ts.iteration)[0]
    return fn


def test_loss_and_logits_agree_with_the_reference(tiny):
    """Float32 against float32: 1e-5 relative on the loss and 2e-5 of the
    largest logit are a few roundings of sums ordered differently (the
    flash-free XLA attention)."""
    cfg, build, model, reference = tiny
    batch = build.check_batch(cfg, 5, 4)
    ts = model.train_state
    want = float(reference.loss(cfg, ts.params, ts.model_state,
                                (batch.features,), (batch.labels,)))
    got = float(model.score(batch))
    assert np.isfinite(want) and abs(got - want) / want < 1e-5
    assert (np.asarray(batch.labels)[:, -1] == -1).all()
    logits = np.asarray(model.output(batch.features))
    ref = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                      (batch.features,)))
    assert logits.shape == (4, cfg["seq_len"], cfg["vocab_size"])
    assert np.abs(logits - ref).max() < 2e-5 * np.abs(ref).max()


def test_every_gradient_leaf_agrees_with_the_reference(gradients):
    """1e-4 of each leaf's norm (the issue's limit for a float32 system):
    float32 against float32, sums ordered differently."""
    _, _, got, want = gradients
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want) > 60
    for path, g in flat_got:
        w = flat_want[path]
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, path                 # every parameter is reached
        assert float(jnp.linalg.norm(g - w)) < 1e-4 * scale, path


def test_fit_trains_the_zoo_model_and_its_edges_round_trip(tiny):
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph.config import (
        ComputationGraphConfiguration)
    cfg, build, _, _ = tiny
    conf = build.zoo_model(cfg, 3).conf()
    text = conf.to_json()
    again = ComputationGraphConfiguration.from_json(text)
    assert again.to_json() == text
    # the sharing is in the configuration: what block 4 and block 5 emit
    # reaches blocks 6 and 7 as edges, the table reaches the head
    inputs = {n.name: n.inputs for n in again.nodes}
    assert inputs["block6"] == ("block5", "block4:memory")
    assert inputs["block7"] == ("block6", "block5:k", "block5:v")
    assert inputs["lm_head"] == ("block7", "embed:table")
    assert [again.node(f"block{l}").layer.layer_index
            for l in cfg["layer_indices"]] == cfg["layer_indices"]
    assert again.node("block1").layer.window == 5
    assert again.node("block5").layer.window is None
    model = ComputationGraph(again).init(3)
    assert model.num_params() == build.parameter_count(cfg)["on_the_chip"]
    assert "StateSpaceHybridBlock" in model.summary()
    data = build.train_set(cfg, 3, 2)
    model.fit(data, epochs=1)
    first = model.score()
    model.fit(data, epochs=8)
    assert model.score() < first - 0.3


def test_an_edge_nobody_emits_is_refused():
    from deeplearning4j_tpu.zoo.models import Phi4MiniFlash
    small = dict(vocab_size=31, hidden_size=16, intermediate_size=16,
                 num_hidden_layers=8, num_attention_heads=4,
                 num_key_value_heads=2, seq_len=8)
    with pytest.raises(ValueError, match="memory"):
        Phi4MiniFlash(layer_indices=(0, 1, 6), **small).conf()
    with pytest.raises(ValueError, match="'k'"):
        Phi4MiniFlash(layer_indices=(4, 6, 7), **small).conf()
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.decoder import (StateSpaceHybridBlock,
                                                      TokenEmbedding)
    g = (NeuralNetConfiguration.Builder().graph_builder().add_inputs("ids")
         .set_input_types(InputType.recurrent(1, 8)))
    g.add_layer("embed", TokenEmbedding(vocab_size=31, n_out=16), "ids")
    block = StateSpaceHybridBlock(n_out=16, mixer="gated_memory", d_inner=32,
                                  mlp_hidden=16)
    with pytest.raises(ValueError, match="exactly 2"):
        g.add_layer("b", block, "embed")
    g.add_layer("b", block, "embed", "embed:table")     # not emitted
    with pytest.raises(ValueError, match="no layer node emits"):
        g.set_outputs("b").build()


def test_the_tied_matrix_is_one_leaf_and_its_gradient_the_sum_of_both_uses(
        tiny, gradients):
    cfg, build, model, _ = tiny
    ts = model.train_state
    assert sorted(ts.params["lm_head"]) == ["norm"]     # no matrix of its own
    table = ts.params["embed"]["W"]
    assert table.shape == (cfg["vocab_size"], cfg["hidden_size"])
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(ts.params)]
    assert shapes.count(table.shape) == 1
    assert model.num_params() == sum(int(np.prod(s)) for s in shapes)
    # Adam holds one pair of moments for it
    moments = [leaf for leaf in jax.tree_util.tree_leaves(ts.opt_state)
               if getattr(leaf, "shape", None) == table.shape]
    assert len(moments) == 2
    # the two uses, told apart by stopping the gradient of the other
    ids, labels, whole, _ = gradients
    node = model._nodes["embed"]

    def loss_with(stop):
        real = node.layer.apply

        def apply(params, state, x, ctx):
            (y, emitted), s = real(params, state, x, ctx)
            if stop == "head":
                emitted = jax.lax.stop_gradient(emitted)
            else:
                y = jax.lax.stop_gradient(y)
            return (y, emitted), s

        def fn(params):
            object.__setattr__(node.layer, "apply", apply)
            try:
                return system_loss(model)(params, ids, labels)
            finally:
                object.__delattr__(node.layer, "apply")
        return fn

    as_embedding = jax.jit(jax.grad(loss_with("head")))(ts.params)
    as_head = jax.jit(jax.grad(loss_with("embedding")))(ts.params)
    g = whole["embed"]["W"]
    parts = as_embedding["embed"]["W"] + as_head["embed"]["W"]
    assert float(jnp.linalg.norm(as_embedding["embed"]["W"])) > 0
    assert float(jnp.linalg.norm(as_head["embed"]["W"])) > 0
    np.testing.assert_allclose(g, parts, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("reader,emitter,leaves", [
    ("block7", "block5", ("W_qkv", "b_qkv")),
    ("block6", "block4", ("A_log", "b_dt", "W_x", "W_dt", "W_in", "conv_w",
                          "D")),
], ids=["cross_attention_to_layer17_keys_and_values",
        "gated_memory_to_layer16_scan"])
def test_the_cross_decoder_sends_gradient_to_what_it_re_reads(
        tiny, gradients, reader, emitter, leaves):
    """With the residual stream cut after the emitter's pair, the
    emitter's parameters are reached through the shared edge alone; the
    reference says the same, to 1e-4."""
    cfg, build, model, reference = tiny
    ts = model.train_state
    ids, labels, got, want = gradients
    # the share that goes through the edge: the gradient with the reader's
    # own parameters' paths intact and the edge stopped, taken away
    node = model._nodes[reader]
    real = node.layer.apply

    def apply(params, state, x, ctx):
        h, *extras = x
        return real(params, state,
                    (h, *(jax.lax.stop_gradient(e) for e in extras)), ctx)

    object.__setattr__(node.layer, "apply", apply)
    try:
        cut = jax.jit(jax.grad(system_loss(model)))(ts.params, ids, labels)
    finally:
        object.__delattr__(node.layer, "apply")
    for leaf in leaves:
        g, w, c = (tree[emitter]["mixer"][leaf] for tree in (got, want, cut))
        assert float(jnp.linalg.norm(g - w)) < 1e-4 * float(
            jnp.linalg.norm(w)), leaf
        through_edge = float(jnp.linalg.norm(g - c))
        assert through_edge > 1e-3 * float(jnp.linalg.norm(g)), leaf


def test_the_key_and_value_columns_get_gradient_from_the_cross_layer_alone(
        tiny, gradients):
    """Layer 17's W_k and W_v columns feed its own maps and layer 19's;
    with its own output projection's gradient stopped they still move."""
    cfg, build, model, _ = tiny
    ts = model.train_state
    ids, labels = gradients[:2]
    node = model._nodes["block5"]
    real = node.layer.apply

    def apply(params, state, x, ctx):
        (y, k, v), s = real(params, state, x, ctx)
        # the block's own output carries nothing of this block back
        return (x + jax.lax.stop_gradient(y - x), k, v), s

    object.__setattr__(node.layer, "apply", apply)
    try:
        g = jax.jit(jax.grad(system_loss(model)))(ts.params, ids, labels)
    finally:
        object.__delattr__(node.layer, "apply")
    h, hk, dh = 4, 2, 8
    w = g["block5"]["mixer"]["W_qkv"]
    assert float(jnp.abs(w[:, :h * dh]).max()) == 0          # queries: none
    assert float(jnp.abs(w[:, h * dh:(h + hk) * dh]).max()) > 0      # keys
    assert float(jnp.abs(w[:, (h + hk) * dh:]).max()) > 0          # values


def test_logits_over_the_slice_are_the_uncut_models_columns(tiny):
    """A sliced vocabulary is a smaller vocabulary: on ids from the slice,
    the logits of a model with the first 97 rows of a 776-row table are
    columns 0-96 of the uncut reference's logits, and the other 679
    columns are what the other seven chips of the deployment hold."""
    cfg, build, model, reference = tiny
    ts = model.train_state
    whole_vocab = cfg["published"]["vocab_size"]
    rest = 0.02 * jax.random.normal(
        jax.random.PRNGKey(1), (whole_vocab - cfg["vocab_size"],
                                cfg["hidden_size"]))
    uncut = {**ts.params, "embed": {"W": jnp.concatenate(
        [ts.params["embed"]["W"], rest])}}
    ids = build.check_batch(cfg, 9, 2).features
    assert int(np.max(ids)) < cfg["vocab_size"]
    whole = np.asarray(reference.logits(
        {**cfg, "vocab_size": whole_vocab}, uncut, ts.model_state, (ids,)))
    assert whole.shape[-1] == whole_vocab
    sliced = np.asarray(model.output(ids))
    np.testing.assert_allclose(sliced, whole[..., :cfg["vocab_size"]],
                               rtol=2e-5, atol=2e-6)


def test_the_mixers_follow_the_published_pattern():
    cell = cells.resolve_cell(CELL)
    build, reference = cells.load_build(cell), cells.load_reference(cell)
    model = build.zoo_model(cell.config)
    kinds = [model.mixer_of(l) for l in range(32)]
    assert [k[0] for k in kinds[:16]] == ["mamba", "attention"] * 8
    assert all(k[2] == 512 for k in kinds[1:16:2])
    assert kinds[16] == ("mamba", True, None)
    assert kinds[17] == ("attention", True, None)
    assert [k[0] for k in kinds[18:]] == ["gated_memory",
                                          "cross_attention"] * 7
    names = {"gated_memory": "gmu", "cross_attention": "cross"}
    for l, (kind, emits, window) in enumerate(kinds):
        assert reference.mixer_of(cell.config, l) == (
            names.get(kind, kind), emits, window)
    assert build._kinds(cell.config) == {
        "mamba": 2, "window_attention": 1, "attention": 1,
        "gated_memory": 1, "cross_attention": 1}


def test_the_files_parameter_table_is_the_builders_count():
    cell = cells.resolve_cell(CELL)
    build = cells.load_build(cell)
    count = build.parameter_count(cell.config)
    assert count["on_the_chip"] == 697_094_272
    for key, value in count.items():
        assert cell.config["parameters"][key] == value, key
    # the issue's table
    assert count["mamba_mixer"] == 41_241_600
    assert count["self_attention_mixer"] == 19_668_864
    assert count["gated_memory_unit_mixer"] == 26_214_400
    assert count["cross_attention_mixer"] == 13_112_704
    assert count["mlp_and_two_layernorms_per_block"] == 78_643_200 + 10_240
    whole = (9 * count["mamba_mixer"] + 9 * count["self_attention_mixer"]
             + 7 * count["gated_memory_unit_mixer"]
             + 7 * count["cross_attention_mixer"]
             + 32 * count["mlp_and_two_layernorms_per_block"]
             + 200_064 * 2560 + 5120)
    assert 3.8e9 < whole < 3.9e9                    # the card's 3.8B
    flops = build.train_flops_per_example(cell.config)
    assert 37.0e12 < flops < 38.0e12
    scan_flops, scan_bytes = build.ssm_scan_work(cell.config)
    assert scan_flops / 197e12 < scan_bytes / 819e9     # bound by bytes
    win_flops, win_bytes = build.window_attention_work(cell.config)
    assert win_flops / 197e12 > win_bytes / 819e9       # bound by operations
    # in-window pairs only: a sixteenth of the sequence each side of 1/8
    full = build._attention_flops(cell.config, 8192 * 8193 // 2)
    assert 0.11 < win_flops / 3 / full < 0.13


def test_the_configuration_file_states_its_source_cuts_and_limit():
    """What ``test_cells.py`` holds of every configuration's file, held
    here for this one too: its own case trips over a pattern that reads
    the ``hidden`` of ``num_hidden_layers`` as a width (PERF.md §7), and
    stops before these."""
    manifest = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in manifest["configs"]
              if c["name"] == "phi4-mini-flash-vp8"]
    body = json.loads((cells.ROOT / entry["file"]).read_text())
    assert body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                   "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 32,
                                 "vocab_size": 200064}
    assert all(k in body and body[k] < body["published"][k]
               for k in body["reduced"])
    assert body["layer_indices"] == [0, 1, 16, 17, 18, 19]
    assert len(body["layer_indices"]) == body["num_hidden_layers"]
    # every number of the catalog's config that is not reduced
    published = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
                 "intermediate_size": 10240, "layer_norm_eps": 1e-05,
                 "max_position_embeddings": 262144, "mb_per_layer": 2,
                 "model_type": "phi4flash", "num_attention_heads": 40,
                 "num_key_value_heads": 20, "resid_pdrop": 0,
                 "sliding_window": 512, "tie_word_embeddings": True,
                 "mlp_bias": False, "lm_head_bias": False}
    assert {k: body[k] for k in published} == published
    assert body["vocab_size"] * 8 == body["published"]["vocab_size"]
    for key in ("assumed", "batch", "departures", "deployment",
                "reduced_why", "parameters"):
        assert body[key], key
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "differential_attention",
                "attention_biases", "initial_values"):
        assert key in body["assumed"], key
    assert "float8" in body["loss_tolerance_why"]
    workload, = [w for w in manifest["workloads"]
                 if w["config"] == "phi4-mini-flash-vp8"]
    assert workload == {**workload, "traffic": "fit-seq8k", "chips": 1}


@pytest.mark.parametrize("control,least,most", [
    ({}, 0.0, 0.0),
    ({"control_operand_dtype": "bfloat16"}, 1e-4, 3e-2),
    ({"control_operand_dtype": "float8_e4m3fn"}, 3e-2, 1.0),
], ids=["none", "operands_bfloat16", "operands_float8"])
def test_the_references_controls_round_what_they_say(tiny, control, least,
                                                     most):
    cfg, build, model, reference = tiny
    ts = model.train_state
    ids = (jnp.asarray(build.check_batch(cfg, 9, 2).features),)
    want = reference.logits(cfg, ts.params, ts.model_state, ids)
    got = reference.logits({**cfg, **control}, ts.params, ts.model_state,
                           ids)
    apart = float(jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.std(want))
    assert least <= apart <= most


def test_the_reference_imports_nothing_of_the_systems_layers_or_kernels():
    text = (cells.ROOT / "yardstick" / "reference"
            / "phi4_mini_flash.py").read_text()
    assert "deeplearning4j_tpu" not in text.split('"""', 2)[2]
    assert "pallas" not in text.split('"""', 2)[2]
