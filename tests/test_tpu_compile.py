"""Kernels of the main path compiled at their real widths for a TPU v5e
that is described, not attached: what interpret mode cannot show (tiles
the compiler refuses for the fast memory they need). Nothing runs, so
nothing here says a word about results or times.

The topology is described inside a fixture, after a test of this file has
started: only the worker that is given this file loads the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _flash_cases():
    """(heads, positions, head, value head, visibility): causal heads of
    64, 256 and 512 at 8,192 positions, the cells' attention calls
    (``test_pallas_kernels.CELL_ATTENTION``), and block diffusion over
    halves of 600, whose query tile (600) is not whole lanes."""
    from deeplearning4j_tpu.ops.visibility import BlockDiffusion, Causal
    from test_pallas_kernels import CELL_ATTENTION
    heads = {"sdar.block_diffusion": 32, "qwen3-next.gated": 16,
             "phi4-mini-flash.full": 40, "phi4-mini-flash.window": 40}
    cases = {f"{h}-{dh}": (h, 8192, dh, dh, Causal())
             for h, dh in ((12, 64), (16, 256), (16, 512))}
    for cell, (t, dh, dv, vis) in CELL_ATTENTION.items():
        cases[cell] = (heads.get(cell, 32), t, dh, dv, vis)
    cases["block_diffusion_tile_of_600"] = (2, 1200, 128, 128,
                                            BlockDiffusion(600, 4))
    return cases


FLASH_CASES = _flash_cases()


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_compiles_with_its_default_tiles(
        one_chip, no_compile_cache, case):
    """The forward kernel and the one backward kernel, bfloat16, at their
    default tiles: BERT-like causal heads of 64, the gated attention's 16
    heads of 256 (Qwen3-Next's; 1024 x 1024 tiles are refused there: 18.5
    MB of scoped VMEM), 512, and every cell's call with its visibility;
    the backward holds a head's whole dQ in VMEM under the scoped limit
    it asks for, its per-query rows and key column in legal blocks."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    heads, t, dh, dv, vis = FLASH_CASES[case]
    qk = jax.ShapeDtypeStruct((1, t, heads, dh), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, t, heads, dv), jnp.bfloat16,
                             sharding=one_chip)

    def total(q, k, v):
        return jnp.sum(flash_attention(q, k, v, visibility=vis,
                                       interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_a_flash_backward_whose_dq_passes_the_scoped_cap_is_two_launches(
        one_chip, no_compile_cache):
    """131,072 positions of one causal head of 128, bfloat16: a head's
    dQ, 128 MiB in float32 and its output block, does not fit in scoped
    VMEM, so the backward is the dK/dV kernel and the dQ kernel, each
    inside Mosaic's default limit."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        SCOPED_VMEM_CAP, _bwd_vmem_need, flash_attention)
    from deeplearning4j_tpu.ops.visibility import Causal
    assert _bwd_vmem_need(131072, 128, 128, 1024, 1024, 2) > SCOPED_VMEM_CAP
    x = jax.ShapeDtypeStruct((1, 131072, 1, 128), jnp.bfloat16,
                             sharding=one_chip)

    def total(q, k, v):
        return jnp.sum(flash_attention(q, k, v, visibility=Causal(),
                                       interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_held_experts_block_loop_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache):
    """Forward and backward of ``held_experts_ffn`` under ``jax.checkpoint``
    at 8,192 tokens, top-10 of 512 with 32 held, bfloat16: grouped
    products inside loops with a traced trip count, every operation of
    which the TPU's compiler leaves under a ``moe.*`` scope, and so the
    zeroing of the 81,920-row buffers before them, in no more memory than
    the 1.64 GB the one 81,920-row buffer took."""
    from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
    from deeplearning4j_tpu.parallel.moe import held_experts_ffn
    t, d, e, g, f, k = 8192, 2048, 512, 32, 512, 10
    shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
              for s in ((t, d), (d, e), (g, d, f), (g, d, f), (g, f, d))]

    def total(*a):
        y, _ = jax.checkpoint(lambda *b: held_experts_ffn(
            *b, tuple(range(g)), top_k=k))(*a)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(total, argnums=range(5))).lower(
        *shapes).compile()
    text = compiled.as_text()
    table = scopes_in_hlo(text)
    loops = {n: op for n, op in table.items() if "/while" in op}
    assert any("transpose(" not in op for op in loops.values())
    assert any("transpose(" in op for op in loops.values())
    assert not [op for op in loops.values() if "moe." not in op]
    unnamed = [line.split(" = ")[0].strip() for line in text.splitlines()
               if " = bf16[81920," in line and " broadcast(" in line
               and "moe." not in line]
    assert not unnamed
    assert compiled.memory_analysis().temp_size_in_bytes < 1.64e9


@pytest.mark.parametrize("window", [512, None])
def test_differential_attention_kernels_compile_at_the_published_widths(
        one_chip, no_compile_cache, window):
    """The forward and the one backward kernel as ``DifferentialAttention``
    calls them at Phi-4-mini-flash's widths: both maps as 40 heads of 64 with a
    value of 128, 8,192 positions, bfloat16; under the window of 512 the
    tiles are 512 x 512 and the inner grid axis two key blocks long."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    from deeplearning4j_tpu.ops.visibility import Causal
    qk = jax.ShapeDtypeStruct((1, 8192, 40, 64), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 40, 128), jnp.bfloat16,
                             sharding=one_chip)

    def total(q, k, v):
        return jnp.sum(flash_attention(q, k, v, visibility=Causal(window),
                                       interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_windowed_gated_attention_kernels_compile_at_the_published_widths(
        one_chip, no_compile_cache):
    """The forward and the one backward kernel as
    ``GatedAttention(window=2048)`` calls them at Trinity-Mini's widths:
    32 heads of 128 (the 4 key/value heads repeated), 8,192 positions,
    bfloat16; a window wider than the
    1024 x 1024 tile keeps the tile, and a query block visits 3 key
    blocks (21 of 64 a head)."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _default_blocks, flash_attention, flash_kv_blocks)
    from deeplearning4j_tpu.ops.visibility import Causal
    vis = Causal(2048)
    assert _default_blocks(128, vis) == (1024, 1024)
    assert flash_kv_blocks(8192, 8192, 1024, 1024, vis) == (21, 64)
    x = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def total(q, k, v):
        return jnp.sum(flash_attention(q, k, v, visibility=vis,
                                       interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_latent_attention_compiles_at_the_published_widths(
        one_chip, no_compile_cache, monkeypatch):
    """``LatentAttention`` as GLM-4.7-Flash's layers call it, forward and
    backward, traced for a TPU (the test stands in for
    ``jax.default_backend``): 20 heads whose queries and keys are 192 + 64
    (the one rotary key broadcast to every head) and values 256, latents
    of 768 and 512, 8,192 positions, bfloat16. The flash kernels take the
    head of 256 on 512 x 1024 tiles and launch twice under the layer's
    scope (the forward kernel, and one backward kernel for dQ, dK and
    dV)."""
    from deeplearning4j_tpu.nn.inputs import RecurrentType
    from deeplearning4j_tpu.nn.layers.attention import LatentAttention
    from deeplearning4j_tpu.nn.layers.base import LayerContext
    from deeplearning4j_tpu.observe.scopes import kernel_calls, kernels_in_hlo
    from deeplearning4j_tpu.ops.pallas_kernels import _default_blocks
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _default_blocks(256) == (512, 1024)
    layer = LatentAttention(n_in=2048, n_out=2048, dtype="bfloat16")
    rt = RecurrentType(2048, 8192)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.bfloat16, sharding=one_chip), jax.eval_shape(
            lambda key: layer.initialize(key, rt), jax.random.PRNGKey(0)))
    assert params["W_kva"].shape == (2048, 512 + 64)
    assert params["W_kvb"].shape == (512, 20 * (192 + 256))
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16,
                             sharding=one_chip)

    def total(p, a):
        y, _ = layer.apply(p, {}, a, LayerContext(train=True))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(total, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert kernel_calls(kernels_in_hlo(text), ("attn.latent",)) == {
        "attn.latent": 2}


def test_block_diffusion_kernels_compile_at_the_published_widths(
        one_chip, no_compile_cache):
    """The forward and the one backward kernel under ``BlockDiffusion(8192,
    4)`` as the SDAR cell calls them: 32 heads of 128 over the 16,384 positions
    ``[noisy | clean]``, bfloat16, the tuned 1024 x 1024 tiles; the tile
    mask is built from scalar bounds (Mosaic selects no vector of
    booleans, which interpret mode does not say)."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    from deeplearning4j_tpu.ops.visibility import BlockDiffusion
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def total(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, visibility=BlockDiffusion(8192, 4),
            interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_selective_scan_keeps_no_whole_state_tensor_at_the_cells_shapes(
        one_chip, no_compile_cache):
    """Forward and backward of ``selective_scan_chunked`` at 8,192 tokens
    of 5,120 channels x 16 states: the temporaries stay far under the
    2.7 GB a (T, D, S) float32 tensor would take."""
    from deeplearning4j_tpu.nn.layers.state_space import (
        selective_scan_chunked)
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((1, 8192, 5120), jnp.bfloat16), ((1, 8192, 5120), f32),
        ((5120, 16), f32), ((1, 8192, 16), f32), ((1, 8192, 16), f32))]

    def total(*a):
        return jnp.sum(selective_scan_chunked(*a, chunk_size=64))

    compiled = jax.jit(jax.grad(total, argnums=range(5))).lower(
        *shapes).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def _delta_rule_shapes(one_chip, t=8192, hk=16, hv=32, d=128):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (s((1, t, hk, d), jnp.bfloat16), s((1, t, hk, d), jnp.bfloat16),
            s((1, t, hv, d), jnp.bfloat16), s((1, t, hv), jnp.float32),
            s((1, t, hv), jnp.float32))


def test_gated_delta_rule_kernels_compile_at_the_cells_shapes(
        one_chip, no_compile_cache):
    """Forward (with and without the border states) and backward of the
    gated delta rule's kernels at 8,192 tokens, 32 value and 16 key heads
    of 128, chunks of 64, bfloat16, inside the scoped VMEM they ask for
    (Mosaic's default covers it); what is left of the plain form's batched
    64 x 64 products and of its ``lax.scan`` is nothing: no ``[., 128, 64,
    64]`` temporary, no ``while``. Everything between the inputs and the
    gradients is traced under the caller's scope."""
    import re
    from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
    from deeplearning4j_tpu.ops.pallas_delta_rule import (
        _vmem_need, gated_delta_rule_kernels)
    from deeplearning4j_tpu.ops.pallas_kernels import scoped_vmem_limit

    def rule(*a):
        with jax.named_scope("gdn.scan"):
            return gated_delta_rule_kernels(*a, chunk_size=64,
                                            interpret=False)[0]

    def both(q, k, v, g, beta, do):
        o, vjp = jax.vjp(jax.checkpoint(rule), q, k, v, g, beta)
        return o, vjp(do)

    do = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32,
                              sharding=one_chip)
    compiled = jax.jit(both).lower(*_delta_rule_shapes(one_chip),
                                   do).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert not re.search(r"\[\d+,128,64,64\]", text)
    assert " while(" not in text
    traced = [op for op in scopes_in_hlo(text).values()
              if op.startswith("jit(both)/")]
    assert traced and not [op for op in traced if "gdn.scan" not in op]
    for backward in (False, True):
        assert scoped_vmem_limit(_vmem_need(2, 128, 128, 64,
                                            backward)) is None
    # the border states are the one residual: 268 MB in float32
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


def test_a_gated_deltanet_layer_keeps_its_kernels_under_gdn_scan(
        one_chip, no_compile_cache, monkeypatch):
    """A ``GatedDeltaNet`` at the cell's widths under ``jax.checkpoint``,
    as the decoder block runs it, traced for a TPU (the test stands in for
    ``jax.default_backend``): the forward, the recomputed forward and the
    backward kernel each carry ``gdn.scan`` in their ``op_name``, by the
    join ``yardstick/scopes.py`` makes, so the per-scope readers find
    them; no loop is left in the layer; and the gauge says 128 chunks."""
    from deeplearning4j_tpu.nn.inputs import RecurrentType
    from deeplearning4j_tpu.nn.layers.base import LayerContext
    from deeplearning4j_tpu.nn.layers.linear_attention import GatedDeltaNet
    from deeplearning4j_tpu.observe.registry import default_registry
    from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
    from deeplearning4j_tpu.ops.pallas_delta_rule import GDN_KERNEL_GAUGE
    from yardstick.scopes import in_scope
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, width = 8192, 2048
    layer = GatedDeltaNet(name="block0", n_in=width, n_out=width,
                          dtype="bfloat16")
    params = jax.eval_shape(
        lambda key: layer.initialize(key, RecurrentType(width, t)),
        jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1, t, width), jnp.bfloat16, sharding=one_chip)

    def total(p, a):
        y, _ = jax.checkpoint(lambda p, a: layer.apply(
            p, {}, a, LayerContext(train=True)))(p, a)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(total, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    table = scopes_in_hlo(text)
    kernels = {name: op for name, op in table.items()
               if name.startswith("gdn_delta_rule_")}   # the custom calls
    assert len(kernels) == 3
    assert all(in_scope(op, ("gdn.scan",)) for op in kernels.values())
    assert sum("gdn_delta_rule_bwd" in op and "transpose(" in op
               for op in kernels.values()) == 1
    assert sum("rematted_computation" in op for op in kernels.values()) == 1
    assert not [op for op in table.values() if "/while" in op]
    assert default_registry().gauge(*GDN_KERNEL_GAUGE).get(
        layer="block0") == 128


def test_selective_scan_kernels_compile_at_the_cells_shapes(
        one_chip, no_compile_cache):
    """Forward (with and without the border states) and backward of the
    selective scan's kernels at 8,192 tokens of 5,120 channels x 16 states,
    bfloat16 ``x`` and float32 ``dt``, chunks of 64, inside Mosaic's
    default scoped VMEM: two launches and no loop in the differentiated
    program, everything between the inputs and the gradients traced under
    the caller's scope, and the temporaries (the border states, 42 MB, and
    the partial sums of ``dB`` and ``dC``) far under the 2.7 GB a (T, D, S)
    float32 tensor would take."""
    from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
    from deeplearning4j_tpu.ops.pallas_kernels import scoped_vmem_limit
    from deeplearning4j_tpu.ops.pallas_selective_scan import (
        _vmem_need, selective_scan_kernels)
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((1, 8192, 5120), jnp.bfloat16), ((1, 8192, 5120), f32),
        ((5120, 16), f32), ((1, 8192, 16), f32), ((1, 8192, 16), f32))]

    def scan(*a):
        with jax.named_scope("ssm.scan"):
            return selective_scan_kernels(*a, chunk_size=64,
                                          interpret=False)

    def both(x, dt, a, b, c, dy):
        y, vjp = jax.vjp(scan, x, dt, a, b, c)
        return y, vjp(dy)

    plain = jax.jit(scan).lower(*shapes).compile()
    assert plain.as_text().count("tpu_custom_call") == 1
    compiled = jax.jit(both).lower(*shapes, shapes[1]).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text
    traced = [op for op in scopes_in_hlo(text).values()
              if op.startswith("jit(both)/")]
    assert traced and not [op for op in traced if "ssm.scan" not in op]
    for backward in (False, True):
        assert scoped_vmem_limit(_vmem_need(16, 512, 64, backward)) is None
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_a_mamba_layer_keeps_its_kernels_under_ssm_scan(
        one_chip, no_compile_cache, monkeypatch):
    """A ``MambaMixer`` at the Phi-4-mini-flash cell's widths under
    ``jax.checkpoint``, as the decoder block runs it, traced for a TPU (the
    test stands in for ``jax.default_backend``): the forward, the
    recomputed forward and the backward kernel each carry ``ssm.scan`` in
    their ``op_name``, by the join ``yardstick/scopes.py`` makes, so the
    per-scope readers find them; no loop is left in the layer; and the
    gauge says 128 chunks."""
    from deeplearning4j_tpu.nn.inputs import RecurrentType
    from deeplearning4j_tpu.nn.layers.base import LayerContext
    from deeplearning4j_tpu.nn.layers.state_space import MambaMixer
    from deeplearning4j_tpu.observe.registry import default_registry
    from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
    from deeplearning4j_tpu.ops.pallas_selective_scan import SSM_KERNEL_GAUGE
    from yardstick.scopes import in_scope
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, width = 8192, 2560
    layer = MambaMixer(name="block0", n_in=width, n_out=width, d_inner=5120,
                       d_state=16, d_conv=4, dt_rank=160, dtype="bfloat16")
    params = jax.eval_shape(
        lambda key: layer.initialize(key, RecurrentType(width, t)),
        jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1, t, width), jnp.bfloat16, sharding=one_chip)

    def total(p, a):
        y, _ = jax.checkpoint(lambda p, a: layer.apply(
            p, {}, a, LayerContext(train=True)))(p, a)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(total, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    table = scopes_in_hlo(text)
    kernels = {name: op for name, op in table.items()
               if name.startswith("ssm_selective_scan_")}  # the custom calls
    assert len(kernels) == 3
    assert all(in_scope(op, ("ssm.scan",)) for op in kernels.values())
    assert sum("ssm_selective_scan_bwd" in op and "transpose(" in op
               for op in kernels.values()) == 1
    assert sum("rematted_computation" in op for op in kernels.values()) == 1
    assert not [op for op in table.values() if "/while" in op]
    assert default_registry().gauge(*SSM_KERNEL_GAUGE).get(
        layer="block0") == 128


def test_ssd_scan_kernels_compile_at_the_cells_shapes(
        one_chip, no_compile_cache):
    """Forward (with and without the border states) and backward of the
    Mamba-2 scan's kernels, skip, gate and grouped norm inside, at the
    Nemotron 3 Nano cell's shapes: 8,192 tokens, 64 heads of 64 in 8
    groups, 128 states, bfloat16 ``x``, ``B``, ``C`` and ``z`` and float32
    ``dt``, inside the scoped VMEM the module states: two launches and no
    loop in the differentiated program, everything between the inputs and
    the gradients traced under the caller's scope, no stacked ``[64, 1,
    128, 64, 64]`` rows, no decay matrices and no float32 ``y``, and the
    temporaries (the border states, 134 MB, beside the scalar tiles) far
    under the 17 GB a (T, H, P, S) float32 tensor would take."""
    import re
    from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
    from deeplearning4j_tpu.ops.pallas_kernels import (
        SCOPED_VMEM_CAP, scoped_vmem_limit)
    from deeplearning4j_tpu.ops.pallas_ssd_scan import (
        _Heads, _vmem_need, ssd_scan_kernels)
    f32, bf16 = jnp.float32, jnp.bfloat16
    n, t, h, p, g, s = 1, 8192, 64, 64, 8, 128
    shapes = [jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)
              for shape, kind in (
                  ((n, t, h, p), bf16), ((n, t, h), f32), ((h,), f32),
                  ((n, t, g, s), bf16), ((n, t, g, s), bf16),
                  ((n, t, h * p), bf16), ((h,), f32), ((h * p,), f32))]

    def scan(*a):
        with jax.named_scope("ssd.scan"):
            return ssd_scan_kernels(*a, 1e-5, interpret=False)

    def both(*a):
        o, vjp = jax.vjp(scan, *a[:-1])
        return o, vjp(a[-1])

    plain = jax.jit(scan).lower(*shapes).compile()
    assert plain.as_text().count("tpu_custom_call") == 1
    do = jax.ShapeDtypeStruct((n, t, h * p), bf16, sharding=one_chip)
    compiled = jax.jit(both).lower(*shapes, do).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text
    assert not re.search(
        r"\[64,1,128,64,64\]|\[\d+,128,128\]|f32\[1,8192,4096\]", text)
    traced = [op for op in scopes_in_hlo(text).values()
              if op.startswith("jit(both)/")]
    assert traced and not [op for op in traced if "ssd.scan" not in op]
    for backward in (False, True):
        need = _vmem_need(s, _Heads(h // g, p), backward)
        assert need < SCOPED_VMEM_CAP
        assert scoped_vmem_limit(need) in (None, need)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


def test_a_mamba2_layer_keeps_its_kernels_under_ssd_scan(
        one_chip, no_compile_cache, monkeypatch):
    """A ``SingleMixerBlock`` with the Mamba-2 mixer at the Nemotron 3 Nano
    cell's widths, with ``recompute`` as the cell runs it, traced for a TPU
    (the test stands in for ``jax.default_backend``): the forward and the
    backward kernel each carry ``ssd.scan`` in their ``op_name``, by the
    join ``yardstick/scopes.py`` makes, so the per-scope readers find
    them; the recomputation launches none (the block keeps what the
    forward wrote); no loop is left in the layer; ``x``,
    ``z`` and the normed rows go to and from the kernels as the layer has
    them, with no transpose of a (T, 4096) array and no (T, H, P) copy
    between; and the gauge says 64 chunks."""
    import re
    from deeplearning4j_tpu.nn.inputs import RecurrentType
    from deeplearning4j_tpu.nn.layers.base import LayerContext
    from deeplearning4j_tpu.nn.layers.decoder import MAMBA2, SingleMixerBlock
    from deeplearning4j_tpu.observe.registry import default_registry
    from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
    from deeplearning4j_tpu.ops.pallas_ssd_scan import SSD_KERNEL_GAUGE
    from yardstick.scopes import in_scope
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, width = 8192, 2688
    block = SingleMixerBlock(name="block0", n_out=width, mixer=MAMBA2,
                             dtype="bfloat16", recompute=True)
    rt = RecurrentType(width, t)
    params = jax.eval_shape(lambda key: block.initialize(key, rt),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1, t, width), jnp.bfloat16, sharding=one_chip)

    def total(p, a):
        y, _ = block.apply(p, {}, a, LayerContext(train=True))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(total, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    table = scopes_in_hlo(text)
    kernels = {name: op for name, op in table.items()
               if name.startswith("ssd_scan_")}         # the custom calls
    assert len(kernels) == 2
    assert all(in_scope(op, ("ssd.scan",)) for op in kernels.values())
    assert sum("ssd_scan_bwd" in op and "transpose(" in op
               for op in kernels.values()) == 1
    assert sum("rematted_computation" in op for op in kernels.values()) == 0
    assert not [op for op in table.values() if "/while" in op]
    moved = [line for line in text.splitlines()
             if re.search(r"= \w+\[[\d,]*8192,4096\]\S* transpose\(", line)
             or re.search(r"= \w+\[[\d,]*4096,8192\]\S* transpose\(", line)]
    assert not moved
    assert not re.search(r"= \w+\[1,8192,64,64\]", text)
    assert default_registry().gauge(*SSD_KERNEL_GAUGE).get(
        layer="block0") == 64


def _recomputing_blocks():
    """``{case: (block, positions, kernel scope, kernels, recomputed)}``:
    a recomputing decoder block of each kind at its cell's mixer widths
    (the expert layer small: it is not what is read here)."""
    from deeplearning4j_tpu.nn.layers import decoder as d
    experts = dict(num_experts=16, held_experts=(0, 1), expert_hidden=256,
                   top_k=2, dtype="bfloat16", recompute=True)
    return {
        "sdar": (d.HybridDecoderBlock(
            name="block0", n_out=2048, mixer=d.BLOCK_DIFFUSION_ATTENTION,
            n_heads=32, n_kv_heads=4, head_dim=128,
            partial_rotary_factor=1.0, rope_theta=1e6, block_length=4,
            **experts), 16384, "attn.block_diffusion", 2, 0),
        "qwen3-next": (d.HybridDecoderBlock(
            name="block3", n_out=2048, mixer=d.GATED_ATTENTION, n_heads=16,
            n_kv_heads=2, head_dim=256, **experts),
         8192, "attn.gated", 2, 0),
        "phi4-mini-flash": (d.StateSpaceHybridBlock(
            name="block17", n_out=2560, mixer=d.ATTENTION, emit=True,
            layer_index=17, n_heads=40, n_kv_heads=20, head_dim=64,
            mlp_hidden=10240, dtype="bfloat16", recompute=True),
         8192, "attn.full", 2, 0),
        "qwen3-next-deltanet": (d.HybridDecoderBlock(
            name="block0", n_out=2048, mixer=d.GATED_DELTANET, **experts),
         8192, "gdn.scan", 2, 0),
        "phi4-mini-flash-mamba": (d.StateSpaceHybridBlock(
            name="block0", n_out=2560, mixer=d.MAMBA, layer_index=0,
            d_inner=5120, d_state=16, d_conv=4, dt_rank=160,
            mlp_hidden=10240, dtype="bfloat16", recompute=True),
         8192, "ssm.scan", 2, 0),
        "nemotron3-nano-mamba2": (d.SingleMixerBlock(
            name="block0", n_out=2688, mixer=d.MAMBA2, dtype="bfloat16",
            recompute=True), 8192, "ssd.scan", 2, 0),
    }


@pytest.mark.parametrize("case", [
    "sdar", "qwen3-next", "phi4-mini-flash", "qwen3-next-deltanet",
    "phi4-mini-flash-mamba", "nemotron3-nano-mamba2"])
def test_a_recomputing_block_runs_its_flash_forward_once(
        one_chip, no_compile_cache, monkeypatch, case):
    """A decoder block with ``recompute`` through its own
    ``apply(train=True)`` under ``jax.grad``, traced for a TPU (the test
    stands in for ``jax.default_backend``): an attention mixer launches
    two kernels under its scope (forward, and dQ with dK/dV; three where the
    forward kernel runs again for its result and logsumexp) and none in the
    recomputation; so do the DeltaNet, Mamba and Mamba-2 blocks, whose
    scan kernels' results and residuals the policy names too. Counted as
    the program's gauge counts them."""
    from deeplearning4j_tpu.nn.inputs import RecurrentType
    from deeplearning4j_tpu.nn.layers.base import LayerContext
    from deeplearning4j_tpu.observe.scopes import kernel_calls, kernels_in_hlo
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    block, t, scope, expected, recomputed = _recomputing_blocks()[case]
    width = block.n_out
    rt = RecurrentType(width, t)

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda key: block.initialize(key, rt), jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: block.init_state(rt))
    x = jax.ShapeDtypeStruct((1, t, width), jnp.bfloat16, sharding=one_chip)

    def total(p, a):
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), state)
        y, _ = block.apply(p, zeros, a, LayerContext(train=True))
        return sum(jnp.sum(v.astype(jnp.float32) ** 2)
                   for v in (y if isinstance(y, tuple) else (y,)))

    text = jax.jit(jax.grad(total, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = kernels_in_hlo(text)
    assert kernel_calls(kernels, (scope,)) == {scope: expected}
    assert len(kernels) == expected
    assert sum("rematted_computation" in op
               for op in kernels.values()) == recomputed
