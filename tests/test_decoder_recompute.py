"""What a recomputing decoder block keeps for its backward pass: its
arguments and, where the mixer ran the flash kernel or a scan kernel
(interpreted here), what that kernel's forward wrote, by name, so the
forward kernel is not launched again; the numbers are those of the block
without ``jax.checkpoint``. And the counter that says so of a compiled
step."""

import contextlib
import functools
import io
import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.observe import scopes
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.ops import pallas_delta_rule as gdn
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import pallas_selective_scan as ssm
from deeplearning4j_tpu.ops import pallas_ssd_scan as ssd

T, WIDTH = 64, 32
VISIBILITIES = {"causal": pk.Causal(), "window": pk.Causal(16),
                "block_diffusion": pk.BlockDiffusion(T // 2, 4)}


def block_under(kind, recompute):
    """A small block whose mixer asks for ``VISIBILITIES[kind]``."""
    if kind == "window":
        return decoder.StateSpaceHybridBlock(
            name="b", n_in=WIDTH, n_out=WIDTH, mixer=decoder.ATTENTION,
            layer_index=1, n_heads=4, n_kv_heads=2, head_dim=8, window=16,
            mlp_hidden=48, recompute=recompute)
    diffusion = kind == "block_diffusion"
    return decoder.HybridDecoderBlock(
        name="b", n_in=WIDTH, n_out=WIDTH,
        mixer=(decoder.BLOCK_DIFFUSION_ATTENTION if diffusion
               else decoder.GATED_ATTENTION),
        n_heads=4, n_kv_heads=2, head_dim=8, partial_rotary_factor=1.0,
        block_length=4, num_experts=4, held_experts=(0, 1, 2, 3),
        expert_hidden=16, top_k=2, recompute=recompute)


@pytest.fixture()
def on_the_flash_kernel(monkeypatch):
    """The layers' attention call takes the Pallas path, as on a TPU from
    1,024 positions on (the kernels interpret on the CPU)."""
    monkeypatch.setattr(pk, "attention", functools.partial(
        pk.attention, prefer_flash=True))


def loss_of(block, squared=False):
    rt = RecurrentType(WIDTH, T)
    params = block.initialize(jax.random.PRNGKey(0), rt)
    state = block.init_state(rt)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, WIDTH))

    def loss(p, a):
        y, _ = block.apply(p, state, a, LayerContext(train=True))
        return jnp.sum(y * y if squared else y)     # plain: keeps nothing

    return loss, params, x


@pytest.mark.parametrize("kind", VISIBILITIES)
def test_a_recomputing_block_gives_the_plain_blocks_loss_and_gradients(
        on_the_flash_kernel, kind):
    results = []
    for recompute in (False, True):
        loss, params, x = loss_of(block_under(kind, recompute), squared=True)
        results.append(jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            params, x))
    (plain, plain_grads), (kept, kept_grads) = results
    assert plain == kept
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        plain_grads, kept_grads)
    assert all(jax.tree.leaves(same)), same


def saved_residuals(loss, *args):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        jax.ad_checkpoint.print_saved_residuals(loss, *args)
    return out.getvalue().splitlines()


@pytest.mark.parametrize("kind", VISIBILITIES)
def test_a_recomputing_block_keeps_its_arguments_and_the_two_named_results(
        on_the_flash_kernel, kind):
    loss, params, x = loss_of(block_under(kind, recompute=True))
    lines = saved_residuals(loss, params, x)
    kept = [line for line in lines if " from the argument " not in line
            and not line.endswith("from a constant")]
    assert len(kept) == 2 and len(lines) > 2, lines
    heads = 4
    lse, = (line for line in kept if line.startswith(f"f32[1,{heads},{T}] "))
    assert f"named '{pk.FLASH_LSE_NAME}'" in lse
    # the result feeds the forward pass too, and jax puts a
    # ``reduce_precision`` (to the value's own type) between such a
    # residual's producer, here the tag, and its readers: the printer names
    # that operation and the function it stands in
    out, = (line for line in kept if line.startswith(f"f32[1,{heads},{T},"))
    assert (f"named '{pk.FLASH_OUT_NAME}'" in out
            or ("output of reduce_precision" in out
                and "(flash_attention)" in out)), out
    saved = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x))
    assert saved.count(f"name[name={pk.FLASH_OUT_NAME}]") == 1
    assert saved.count("pallas_call") == 2


def test_on_the_xla_attention_path_a_recomputing_block_keeps_no_more():
    """Short sequences and the CPU: nothing is named, nothing but the
    arguments is kept."""
    loss, params, x = loss_of(block_under("causal", recompute=True))
    lines = saved_residuals(loss, params, x)
    assert not [line for line in lines if "named '" in line]
    assert all(" from the argument " in line
               or line.endswith("from a constant") for line in lines), lines


@pytest.mark.parametrize("kind", VISIBILITIES)
def test_outside_a_checkpoint_the_tags_change_nothing_that_is_lowered(
        monkeypatch, kind):
    """``flash_attention`` by itself: the forward's jaxpr holds no tag (they
    sit in the ``custom_vjp``'s forward rule alone), and the program its
    gradient lowers to is the one an untagged rule lowers to."""
    vis = VISIBILITIES[kind]
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(key, (1, T, 2, 8)) for key in ks)

    def total(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, visibility=vis,
                                          block_q=16, block_k=16,
                                          interpret=True) ** 2)

    assert "name[" not in str(jax.make_jaxpr(total)(q, k, v))
    grad = jax.grad(total, argnums=(0, 1, 2))
    assert str(jax.make_jaxpr(grad)(q, k, v)).count("name[") == 2

    def lowered():      # private functions are numbered as they are met
        return re.sub(r"(@\w+?)_\d+\b", r"\1",
                      jax.jit(grad).lower(q, k, v).as_text())

    tagged = lowered()
    monkeypatch.setattr(pk, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()
    assert "name[" not in str(jax.make_jaxpr(grad)(q, k, v))
    assert lowered() == tagged


# a scan mixer's kernel module and the names its forward rule tags, the
# result's first
SCANS = {
    decoder.GATED_DELTANET: (gdn, (gdn.DELTA_RULE_OUT_NAME,
                                   gdn.DELTA_RULE_STATES_NAME,
                                   gdn.DELTA_RULE_TM_NAME)),
    decoder.MAMBA2: (ssd, (ssd.SSD_OUT_NAME, ssd.SSD_BORDERS_NAME)),
    decoder.MAMBA: (ssm, (ssm.SCAN_Y_NAME, ssm.SCAN_STATES_NAME)),
}


def scan_block_under(mixer, recompute):
    """A small block on ``mixer`` at head and state sizes its kernels take
    (lane tiles of 128)."""
    if mixer == decoder.GATED_DELTANET:
        return decoder.HybridDecoderBlock(
            name="b", n_in=WIDTH, n_out=WIDTH, mixer=mixer, n_key_heads=1,
            n_value_heads=2, key_head_dim=128, value_head_dim=128,
            num_experts=4, held_experts=(0, 1, 2, 3), expert_hidden=16,
            top_k=2, recompute=recompute)
    if mixer == decoder.MAMBA2:
        return decoder.SingleMixerBlock(
            name="b", n_in=WIDTH, n_out=WIDTH, mixer=mixer, mamba_heads=2,
            mamba_head_dim=64, n_groups=1, d_state=128, recompute=recompute)
    return decoder.StateSpaceHybridBlock(
        name="b", n_in=WIDTH, n_out=WIDTH, mixer=mixer, d_inner=128,
        d_state=8, dt_rank=3, mlp_hidden=48, recompute=recompute)


@pytest.fixture()
def on_the_scan_kernels(monkeypatch):
    """The scan layers take their Pallas kernels, as on a TPU at these
    sizes (the kernels interpret on the CPU)."""
    for module, _ in SCANS.values():
        monkeypatch.setattr(module, "kernels_take", lambda *a: True)


@pytest.mark.parametrize("mixer", SCANS)
def test_a_recomputing_scan_block_gives_the_plain_blocks_loss_and_gradients(
        on_the_scan_kernels, mixer):
    results = []
    for recompute in (False, True):
        loss, params, x = loss_of(scan_block_under(mixer, recompute),
                                  squared=True)
        results.append(jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            params, x))
    (plain, plain_grads), (kept, kept_grads) = results
    assert plain == kept
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        plain_grads, kept_grads)
    assert all(jax.tree.leaves(same)), same


@pytest.mark.parametrize("mixer", SCANS)
def test_a_recomputing_scan_block_keeps_its_arguments_and_the_named_results(
        on_the_scan_kernels, mixer):
    """Beside its arguments the block keeps exactly what the scan kernel's
    forward wrote, and its gradient launches that kernel once: one forward
    and one backward ``pallas_call``."""
    _, names = SCANS[mixer]
    loss, params, x = loss_of(scan_block_under(mixer, recompute=True))
    lines = saved_residuals(loss, params, x)
    kept = [line for line in lines if " from the argument " not in line
            and not line.endswith("from a constant")]
    assert len(kept) == len(names) and len(lines) > len(names), lines
    for name in names[1:]:
        assert sum(f"named '{name}'" in line for line in kept) == 1, kept
    # the result feeds the forward pass too: the printer may name the
    # ``reduce_precision`` jax puts after the tag (the flash case's reason)
    out, = (line for line in kept
            if not any(f"named '{name}'" in line for name in names[1:]))
    assert (f"named '{names[0]}'" in out
            or ("output of reduce_precision" in out
                and "_kernels)" in out)), out
    saved = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x))
    for name in names:
        assert saved.count(f"name[name={name}]") == 1, name
    assert saved.count("pallas_call") == 2


@pytest.mark.parametrize("mixer", SCANS)
def test_on_the_plain_scan_forms_a_recomputing_block_keeps_no_more(mixer):
    """Narrow heads and the CPU: the plain scans name nothing, and the
    block keeps nothing but its arguments."""
    loss, params, x = loss_of(scan_block_under(mixer, recompute=True))
    lines = saved_residuals(loss, params, x)
    assert not [line for line in lines if "named '" in line]
    assert all(" from the argument " in line
               or line.endswith("from a constant") for line in lines), lines


# a compiled step's text as the TPU's compiler writes it, cut to what is
# read: two attention layers' launches (the second layer's forward run again
# in the recomputation), the values a launch returns, a copy in flight, the
# delta rule's three, XLA's own Mosaic kernel, a fusion
HLO = """
  %jvp_attn.gated_.1 = (bf16[1,16,8192,256], f32[1,16,8192,1]) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn.gated)/pallas_call" stack_frame_id=87}
  %pallas_call.8 = bf16[1,16,8192,256] get-tuple-element(%jvp_attn.gated_.1), index=0, metadata={op_name="jit(step)/jvp(attn.gated)/pallas_call"}
  %attn.gated.2 = (bf16[1,16,8192,256], bf16[1,16,8192,256]) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/attn.gated/pallas_call"}
  %attn.gated.3 = bf16[1,16,8192,256] custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/attn.gated/pallas_call"}
  %copy-start.7 = (bf16[8], bf16[8], u32[]) copy-start(%c), metadata={op_name="jit(step)/jvp(attn.gated)/pallas_call"}
  %jvp_attn.window_.1 = (bf16[1,40,8192,128], f32[1,40,8192,1]) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn.window)/pallas_call"}
  %attn.window.4 = (bf16[1,40,8192,128], f32[1,40,8192,1]) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attn.window/pallas_call"}
  %attn.window.5 = bf16[1,40,8192,64] custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/attn.window/pallas_call"}
  %attn.window.6 = bf16[1,40,8192,64] custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/attn.window/pallas_call"}
  %gdn_delta_rule_fwd.2 = bf16[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(gdn.scan)/gdn_delta_rule_fwd/pallas_call"}
  %gdn_delta_rule_fwd.3 = bf16[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/gdn.scan/gdn_delta_rule_fwd/pallas_call"}
  %gdn_delta_rule_bwd.1 = bf16[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/gdn.scan/gdn_delta_rule_bwd/pallas_call"}
  %ragged-dot-none.4 = bf16[8192,768] custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %custom-call.53 = bf16[8] custom-call(%a), custom_call_target="Gather", metadata={op_name="jit(step)/jvp(moe.combine)/while/body/jit(_take)/gather"}
  ROOT %fusion.9 = bf16[8] fusion(%a), kind=kLoop, metadata={op_name="jit(step)/jvp(attn.gated)/mul"}
"""


def test_the_kernel_launches_of_a_compiled_step_are_counted_by_scope():
    kernels = scopes.kernels_in_hlo(HLO)
    assert sorted(kernels) == [
        "attn.gated.2", "attn.gated.3", "attn.window.4", "attn.window.5",
        "attn.window.6", "gdn_delta_rule_bwd.1", "gdn_delta_rule_fwd.2",
        "gdn_delta_rule_fwd.3", "jvp_attn.gated_.1", "jvp_attn.window_.1"]
    declared = ("gdn.scan", "attn.gated", "attn.window", "attn.full",
                "moe.experts")
    calls = scopes.kernel_calls(kernels, declared)
    assert calls == {"gdn.scan": 3, "attn.gated": 3, "attn.window": 4,
                     "attn.full": 0, "moe.experts": 0}
    # the step's whole table still names every instruction
    assert len(scopes.scopes_in_hlo(HLO)) == 15


def test_the_gauge_carries_a_steps_kernel_launches_by_scope():
    gauge = default_registry().gauge(*scopes.STEP_KERNEL_CALLS_GAUGE)
    gauge._series.clear()
    scopes.publish_kernel_calls({"attn.block_diffusion": 8,
                                 "moe.experts": 0})
    assert gauge.get(scope="attn.block_diffusion") == 8
    assert gauge.get(scope="moe.experts") == 0
    assert gauge.get(scope="attn.gated") is None
    gauge._series.clear()
