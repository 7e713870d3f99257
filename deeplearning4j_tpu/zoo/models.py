"""Model zoo.

Analog of deeplearning4j-zoo (SURVEY §2.6: ZooModel.java:23 + model/
AlexNet, Darknet19, LeNet, ResNet50, SimpleCNN, VGG16, VGG19,
TextGenerationLSTM, TinyYOLO...). Each zoo entry builds a ready
configuration/model for a given input shape + class count.

TPU-first notes: all convs NHWC; ResNet50 uses the standard bottleneck-v1
topology as a ComputationGraph (merge/elementwise vertices), compiled to a
single XLA program. bfloat16 compute is a flag away
(``compute_dtype="bfloat16"``) and is the benchmark configuration.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

from deeplearning4j_tpu.models.computation_graph import ComputationGraph
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph.vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.convolution import (
    ConvolutionLayer,
    ConvolutionMode,
    PoolingType,
    SpaceToDepthLayer,
    SubsamplingLayer,
    ZeroPaddingLayer,
)
from deeplearning4j_tpu.nn.layers.feedforward import (
    ActivationLayer,
    DenseLayer,
    DropoutLayer,
)
from deeplearning4j_tpu.nn.layers.normalization import BatchNormalization
from deeplearning4j_tpu.nn.layers.output import (
    GlobalPoolingLayer,
    LossLayer,
    OutputLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.layers.recurrent import LSTM
from deeplearning4j_tpu.ops.activations import Activation
from deeplearning4j_tpu.ops.initializers import WeightInit
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.optimize.updaters import Adam, Nesterovs, Updater


class ZooModel:
    """Base zoo entry (reference: ZooModel.java:23). ``init()`` returns a
    built, initialized model.

    ``init_pretrained`` implements the reference's download+checksum
    contract (ZooModel.initPretrained:51): fetch the published weights
    archive into the cache dir, verify its Adler32 checksum, restore.
    Zero-egress environments point ``url`` at a ``file://`` mirror (the
    path the tests exercise); a plain local ``path`` also works."""

    # subclasses may publish {url, checksum} per pretrained flavor the
    # way the reference's pretrainedUrl/pretrainedChecksum do
    PRETRAINED: dict = {}

    def conf(self):
        raise NotImplementedError

    def init(self):
        raise NotImplementedError

    def init_pretrained(self, path: Optional[str] = None,
                        url: Optional[str] = None,
                        checksum: Optional[int] = None,
                        flavor: str = "default"):
        from deeplearning4j_tpu.datasets.fetchers import (
            DATA_DIR, fetch_with_mirror)
        from deeplearning4j_tpu.models.serialization import (
            restore_computation_graph, restore_multi_layer_network)
        if path is None:
            if url is None and flavor in self.PRETRAINED:
                spec = self.PRETRAINED[flavor]
                url = spec.get("url")
                checksum = checksum if checksum is not None \
                    else spec.get("checksum")
                res = spec.get("resource")
                if url is None and res is not None:
                    # committed self-trained artifact shipped as package
                    # data (zero-egress stand-in for the reference's
                    # published downloads) — same checksum contract
                    cand = os.path.join(os.path.dirname(
                        os.path.abspath(__file__)), res)
                    if not os.path.exists(cand):
                        raise FileNotFoundError(
                            f"pretrained resource missing: {cand}")
                    import zlib as _z
                    v = 1
                    with open(cand, "rb") as f:
                        for chunk in iter(lambda: f.read(1 << 20), b""):
                            v = _z.adler32(chunk, v)
                    if checksum is not None and v != checksum:
                        raise IOError(
                            f"pretrained resource {res}: Adler32 {v} != "
                            f"expected {checksum}")
                    path = cand
            if path is None and url is None:
                raise FileNotFoundError(
                    "no pretrained weights source: pass path= to a local "
                    "checkpoint zip, or url= (file:// mirrors work in "
                    "zero-egress environments) + checksum=")
            if path is None:
                # cache key includes the url: without it, a later call
                # with a different mirror would silently reuse the first
                # download
                import zlib
                tag = f"{zlib.crc32(url.encode()):08x}"
                dest = os.path.join(
                    DATA_DIR, "pretrained",
                    f"{type(self).__name__}_{flavor}_{tag}.zip")
                path = fetch_with_mirror(url, dest,
                                         expected_checksum=checksum)
        # the checkpoint's stored configuration defines the restored
        # architecture (reference semantics: initPretrained returns the
        # published network as-is); dispatch by this zoo entry's config
        # class without paying a throwaway random init
        from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
        if isinstance(self.conf(), MultiLayerConfiguration):
            return restore_multi_layer_network(path)
        return restore_computation_graph(path)


@dataclasses.dataclass
class LeNet(ZooModel):
    """reference: deeplearning4j-zoo/.../model/LeNet.java (BASELINE cfg 0)."""
    # committed self-trained weights (≥98% on the real UCI digits test
    # split — tests/resources/pretrained/train_artifacts.py), the
    # zero-egress analog of the reference's published MNIST flavor
    PRETRAINED = {"digits": {"resource": "weights/lenet_digits.zip",
                             "checksum": 2574425481}}
    num_classes: int = 10
    height: int = 28
    width: int = 28
    channels: int = 1
    updater: Updater = dataclasses.field(default_factory=lambda: Adam(1e-3))
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        return (NeuralNetConfiguration.Builder()
                .seed(self.seed)
                .updater(self.updater)
                .compute_dtype(self.compute_dtype)
                .list()
                .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                        activation=Activation.RELU,
                                        weight_init=WeightInit.HE_NORMAL))
                .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                        activation=Activation.RELU,
                                        weight_init=WeightInit.HE_NORMAL))
                .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(DenseLayer(n_out=500, activation=Activation.RELU))
                .layer(OutputLayer(n_out=self.num_classes,
                                   loss=LossFunction.MCXENT,
                                   activation=Activation.SOFTMAX))
                .set_input_type(InputType.convolutional_flat(
                    self.height, self.width, self.channels))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class SimpleCNN(ZooModel):
    """reference: model/SimpleCNN.java — 4 conv blocks + dense."""
    # committed self-trained weights (≥95% on the real UCI digits test
    # split, NHWC 28x28x1 — tests/resources/pretrained/
    # train_artifacts.py); the online-learning demo model (ISSUE 10)
    PRETRAINED = {"digits": {"resource": "weights/simplecnn_digits.zip",
                             "checksum": 4047027733}}
    num_classes: int = 10
    height: int = 48
    width: int = 48
    channels: int = 3
    seed: int = 123

    def conf(self):
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Adam(1e-3))
             .list())
        for n_out in (16, 32, 64, 128):
            b = (b.layer(ConvolutionLayer(
                    n_out=n_out, kernel_size=(3, 3),
                    convolution_mode=ConvolutionMode.SAME,
                    activation=Activation.IDENTITY))
                 .layer(BatchNormalization())
                 .layer(ConvolutionLayer(
                     n_out=n_out, kernel_size=(3, 3),
                     convolution_mode=ConvolutionMode.SAME,
                     activation=Activation.RELU))
                 .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2))))
        return (b.layer(DenseLayer(n_out=256, activation=Activation.RELU,
                                   dropout=0.5))
                .layer(OutputLayer(n_out=self.num_classes))
                .set_input_type(InputType.convolutional(
                    self.height, self.width, self.channels))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class VGG16(ZooModel):
    """reference: model/VGG16.java (BASELINE cfg 1)."""
    num_classes: int = 200
    height: int = 224
    width: int = 224
    channels: int = 3
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Nesterovs(1e-2, 0.9))
             .compute_dtype(self.compute_dtype)
             .list())
        plan = [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]
        for n_out, reps in plan:
            for _ in range(reps):
                b = b.layer(ConvolutionLayer(
                    n_out=n_out, kernel_size=(3, 3),
                    convolution_mode=ConvolutionMode.SAME,
                    activation=Activation.RELU))
            b = b.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        return (b.layer(DenseLayer(n_out=4096, activation=Activation.RELU,
                                   dropout=0.5))
                .layer(DenseLayer(n_out=4096, activation=Activation.RELU,
                                  dropout=0.5))
                .layer(OutputLayer(n_out=self.num_classes))
                .set_input_type(InputType.convolutional(
                    self.height, self.width, self.channels))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


def fold_stem_weights(w7):
    """Fold 7×7/2 stem weights (7,7,C,O) HWIO into the exactly
    equivalent 4×4/1 space-to-depth parameterization (4,4,4C,O):
    ``Wf[ku, kv, (a·2+b)·C + c, o] = W7[2ku+a, 2kv+b, c, o]`` (zero
    where 2ku+a > 6). The channel slot order matches
    ``SpaceToDepthLayer(block_size=2)``'s (row, col, channel) packing,
    so restoring a trained conv1 into a ``s2d_stem=True`` ResNet50 (or
    back) is lossless — equivalence asserted in tests/test_zoo_extended."""
    import numpy as np
    w7 = np.asarray(w7)
    kh, kw, c, o = w7.shape
    wf = np.zeros((4, 4, 4 * c, o), w7.dtype)
    for ku in range(4):
        for a in range(2):
            u = 2 * ku + a
            if u >= kh:
                continue
            for kv in range(4):
                for b in range(2):
                    v = 2 * kv + b
                    if v >= kw:
                        continue
                    wf[ku, kv, (a * 2 + b) * c:(a * 2 + b + 1) * c] = \
                        w7[u, v]
    return wf


@dataclasses.dataclass
class ResNet50(ZooModel):
    """reference: model/ResNet50.java (BASELINE cfgs 1 & 4) — bottleneck-v1
    ComputationGraph: conv1 7x7/2 → maxpool/2 → stages [3,4,6,3] →
    global avg pool → softmax."""
    num_classes: int = 200
    height: int = 224
    width: int = 224
    channels: int = 3
    seed: int = 123
    compute_dtype: str = "float32"
    updater: Updater = dataclasses.field(
        default_factory=lambda: Nesterovs(1e-2, 0.9))
    # Build each bottleneck as one FusedBottleneckBlock (plain-XLA convs
    # that return their BN statistics, Gram-matrix statistics for the
    # expanding projections — ops/fused_conv.py conv_bn_stats_xla): same
    # math, fewer HBM passes per BatchNorm. The per-layer graph (default)
    # keeps conv/BN as separate layers, which the TP planner and
    # transfer-learning surgery operate on.
    fused_blocks: bool = False
    # Selects nothing since PR 29 removed the Pallas conv+BN tier: here
    # only because yardstick/configs/resnet50-tiny64/build.py passes it,
    # until a `benchmark` PR drops the key there (ROADMAP D10).
    fused_impl: str = "xla"
    # Space-to-depth stem (round 5, VERDICT r4 #6): rearrange the input
    # H×W×3 → H/2×W/2×12 and replace the 7×7/2 conv1 with the EXACTLY
    # equivalent 4×4/1 conv on 12 channels (fold_stem_weights maps the
    # weights; equivalence-tested). Fattens the 3-channel stem
    # contraction the MXU underfills. Measured effect: PERF_ANALYSIS r5.
    s2d_stem: bool = False

    def __post_init__(self):
        if self.fused_impl != "xla":
            raise ValueError(
                f"ResNet50 fused_impl={self.fused_impl!r}: PR 29 removed "
                f"the Pallas conv+BN tier; fused blocks have one "
                f"implementation, 'xla'")

    def conf(self):
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.updater)
             .compute_dtype(self.compute_dtype)
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def conv_bn(name, src, n_out, k, s, act=Activation.RELU):
            g.add_layer(f"{name}_conv", ConvolutionLayer(
                n_out=n_out, kernel_size=k, stride=s,
                convolution_mode=ConvolutionMode.SAME, has_bias=False,
                weight_init=WeightInit.HE_NORMAL,
                activation=Activation.IDENTITY), src)
            g.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_conv")
            if act is None:
                return f"{name}_bn"
            g.add_layer(f"{name}_act", ActivationLayer(activation=act),
                        f"{name}_bn")
            return f"{name}_act"

        def bottleneck(name, src, filters, stride, downsample):
            if self.fused_blocks:
                from deeplearning4j_tpu.nn.layers.fused import (
                    FusedBottleneckBlock)
                g.add_layer(name, FusedBottleneckBlock(
                    filters=filters, stride=stride, downsample=downsample),
                    src)
                return name
            f1, f2, f3 = filters, filters, filters * 4
            x = conv_bn(f"{name}_a", src, f1, (1, 1), (stride, stride))
            x = conv_bn(f"{name}_b", x, f2, (3, 3), (1, 1))
            x = conv_bn(f"{name}_c", x, f3, (1, 1), (1, 1), act=None)
            if downsample:
                shortcut = conv_bn(f"{name}_ds", src, f3, (1, 1),
                                   (stride, stride), act=None)
            else:
                shortcut = src
            g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x,
                         shortcut)
            g.add_layer(f"{name}_out",
                        ActivationLayer(activation=Activation.RELU),
                        f"{name}_add")
            return f"{name}_out"

        if self.s2d_stem:
            # 7×7/2 SAME on (H,W,3) ≡ 4×4/1 VALID on the s2d tensor
            # padded (1,2)×(1,2): y[i,j] = Σ x[2i+u-2, 2j+v-2]·W[u,v]
            # with u = 2ku+a becomes a stride-1 conv over the 2×2-block
            # channels — same math, fold_stem_weights carries weights
            # between the two parameterizations
            g.add_layer("s2d", SpaceToDepthLayer(block_size=2), "in")
            g.add_layer("s2d_pad", ZeroPaddingLayer(pad=(1, 2, 1, 2)),
                        "s2d")
            g.add_layer("conv1_conv", ConvolutionLayer(
                n_out=64, kernel_size=(4, 4), stride=(1, 1),
                convolution_mode=ConvolutionMode.TRUNCATE,
                padding=(0, 0), has_bias=False,
                weight_init=WeightInit.HE_NORMAL,
                activation=Activation.IDENTITY), "s2d_pad")
            g.add_layer("conv1_bn", BatchNormalization(), "conv1_conv")
            g.add_layer("conv1_act",
                        ActivationLayer(activation=Activation.RELU),
                        "conv1_bn")
            x = "conv1_act"
        else:
            x = conv_bn("conv1", "in", 64, (7, 7), (2, 2))
        g.add_layer("pool1", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        x = "pool1"
        stages = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]
        for si, (filters, blocks, first_stride) in enumerate(stages):
            for bi in range(blocks):
                stride = first_stride if bi == 0 else 1
                x = bottleneck(f"s{si}b{bi}", x, filters, stride, bi == 0)
        g.add_layer("avgpool", GlobalPoolingLayer(
            pooling_type=PoolingType.AVG), x)
        g.add_layer("out", OutputLayer(n_out=self.num_classes,
                                       loss=LossFunction.MCXENT,
                                       activation=Activation.SOFTMAX),
                    "avgpool")
        g.set_outputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


@dataclasses.dataclass
class TextGenerationLSTM(ZooModel):
    """reference: model/TextGenerationLSTM.java — char-level 2xLSTM(256)."""
    # committed self-trained char-level weights (corpus + vocab:
    # tests/resources/pretrained/; weights/textgen_vocab.json maps
    # char → input index, 0 = unknown)
    PRETRAINED = {"default": {"resource": "weights/textgen_lstm.zip",
                              "checksum": 3656007127}}
    vocab_size: int = 77
    timesteps: int = 60
    lstm_units: int = 256
    seed: int = 123

    def conf(self):
        return (NeuralNetConfiguration.Builder()
                .seed(self.seed)
                .updater(Adam(2e-3))
                .gradient_normalization("clip_value", 5.0)
                .list()
                .layer(LSTM(n_out=self.lstm_units,
                            activation=Activation.TANH))
                .layer(LSTM(n_out=self.lstm_units,
                            activation=Activation.TANH))
                .layer(RnnOutputLayer(n_out=self.vocab_size,
                                      loss=LossFunction.MCXENT,
                                      activation=Activation.SOFTMAX))
                .set_input_type(InputType.recurrent(self.vocab_size,
                                                    self.timesteps))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class Qwen3Next(ZooModel):
    """Hybrid linear-attention mixture-of-experts causal language model of
    the Qwen3-Next family (published config:
    huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``config.json``; the
    field names below are its keys). ``num_hidden_layers`` blocks, every
    ``full_attention_interval``-th a gated softmax attention and the others
    Gated DeltaNets, each followed by a mixture of gated experts with one
    shared expert; RMSNorm (zero-centred), an untied head, next-token loss.

    ``held_experts`` (default: all) names the routed experts whose weights
    this chip holds of ``num_experts``: the router keeps ``num_experts``
    outputs and the rest of the mixture is left out (an expert-parallel
    deployment's share, ``nn.layers.feedforward.HeldExpertsMoE``).
    ``vocab_size`` may be a slice of the published vocabulary: embedding,
    head and loss are over the slice. Features are integer token ids
    (N, seq_len), labels ``nn.layers.decoder.next_token_labels(ids)``.
    Not in the model: the multi-token-prediction module, a router auxiliary
    loss, dropout."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    held_experts: Tuple[int, ...] = ()
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    seq_len: int = 8192
    chunk_size: int = 64
    recompute: bool = True
    compute_dtype: str = "bfloat16"
    updater: Optional[Updater] = None
    seed: int = 123

    def conf(self):
        from deeplearning4j_tpu.nn.layers.decoder import (
            GATED_ATTENTION, GATED_DELTANET, CausalLMOutputLayer,
            HybridDecoderBlock, TokenEmbedding)
        std = self.initializer_range
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.updater or Adam(1e-4))
             .compute_dtype(self.compute_dtype)
             .list()
             .layer(TokenEmbedding(name="embed", vocab_size=self.vocab_size,
                                   n_out=self.hidden_size, init_std=std)))
        for l in range(self.num_hidden_layers):
            full = (l + 1) % self.full_attention_interval == 0
            b = b.layer(HybridDecoderBlock(
                name=f"block{l}", n_out=self.hidden_size,
                mixer=GATED_ATTENTION if full else GATED_DELTANET,
                n_heads=self.num_attention_heads,
                n_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                partial_rotary_factor=self.partial_rotary_factor,
                rope_theta=self.rope_theta,
                n_key_heads=self.linear_num_key_heads,
                n_value_heads=self.linear_num_value_heads,
                key_head_dim=self.linear_key_head_dim,
                value_head_dim=self.linear_value_head_dim,
                conv_kernel=self.linear_conv_kernel_dim,
                chunk_size=self.chunk_size,
                num_experts=self.num_experts,
                held_experts=tuple(self.held_experts),
                expert_hidden=self.moe_intermediate_size,
                shared_hidden=self.shared_expert_intermediate_size,
                top_k=self.num_experts_per_tok,
                norm_topk=self.norm_topk_prob, eps=self.rms_norm_eps,
                init_std=std, recompute=self.recompute))
        return (b.layer(CausalLMOutputLayer(
                    name="lm_head", n_out=self.vocab_size,
                    eps=self.rms_norm_eps, init_std=std))
                .set_input_type(InputType.recurrent(1, self.seq_len))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class SDARMoE(ZooModel):
    """Block-diffusion mixture-of-experts language model of the SDAR
    family, trained (published config: huggingface.co/JetLM/
    SDAR-30B-A3B-Chat, ``config.json``, ``model_type: sdar_moe``;
    arXiv:2510.06303, whose training layout is BD3-LM's, arXiv:2503.09573;
    the field names below are the config's keys). A Qwen3-MoE decoder:
    ``num_hidden_layers`` blocks of grouped-query attention (per-head q/k
    RMSNorm, rotary on the whole head, no biases, no gate) and a mixture
    of gated experts with no shared expert; RMSNorm (zero-centred), an
    untied head. It is trained as a masked diffusion over blocks of
    ``block_length``: autoregressive from block to block, bidirectional
    and masked inside a block.

    Features are ``[xt | x0]`` (N, 2 ``seq_len``), the noisy and the clean
    copy of a row of ``seq_len`` ids in one pass under
    ``ops.visibility.BlockDiffusion``; labels (N, ``seq_len``, 2) carry
    the hidden ids and their ``1 / t_n`` weights. Both come from
    ``datasets.diffusion.BlockDiffusionNoiser(mask_token_id)`` set on the
    iterator of clean rows (``mask_token_id`` default: the last id of the
    vocabulary held; data ids lie below it).

    ``held_experts`` (default: all) and a sliced ``vocab_size`` are an
    expert-parallel deployment's share, as ``Qwen3Next``'s.
    ``router_aux_loss_coef`` above 0 adds that many times each layer's
    load-balancing loss over all ``num_experts`` router outputs to the
    training loss (the family's key; its trainers compute the loss once
    over the layers' tokens together, here each layer adds its own). Not
    in the model: dropout, generation (a decode step of this family
    yields a block, not a token)."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    num_experts: int = 128
    held_experts: Tuple[int, ...] = ()
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    block_length: int = 4
    mask_token_id: Optional[int] = None
    seq_len: int = 8192
    recompute: bool = True
    compute_dtype: str = "bfloat16"
    updater: Optional[Updater] = None
    seed: int = 123

    @property
    def mask_id(self) -> int:
        return (self.vocab_size - 1 if self.mask_token_id is None
                else self.mask_token_id)

    def noiser(self, seed: Optional[int] = None, eps: float = 1e-3):
        """The pre-processor that makes this model's batches from clean
        rows: ``iterator.set_pre_processor(model.noiser())``."""
        from deeplearning4j_tpu.datasets.diffusion import BlockDiffusionNoiser
        return BlockDiffusionNoiser(self.mask_id, eps=eps,
                                    seed=self.seed if seed is None else seed)

    def conf(self):
        from deeplearning4j_tpu.nn.layers.decoder import (
            BLOCK_DIFFUSION_ATTENTION, CausalLMOutputLayer,
            HybridDecoderBlock, TokenEmbedding)
        std = self.initializer_range
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.updater or Adam(1e-4))
             .compute_dtype(self.compute_dtype)
             .list()
             .layer(TokenEmbedding(name="embed", vocab_size=self.vocab_size,
                                   n_out=self.hidden_size, init_std=std)))
        for l in range(self.num_hidden_layers):
            b = b.layer(HybridDecoderBlock(
                name=f"block{l}", n_out=self.hidden_size,
                mixer=BLOCK_DIFFUSION_ATTENTION,
                n_heads=self.num_attention_heads,
                n_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                partial_rotary_factor=1.0, rope_theta=self.rope_theta,
                block_length=self.block_length,
                num_experts=self.num_experts,
                held_experts=tuple(self.held_experts),
                expert_hidden=self.moe_intermediate_size, shared_hidden=0,
                top_k=self.num_experts_per_tok,
                norm_topk=self.norm_topk_prob,
                router_aux_loss_coef=self.router_aux_loss_coef,
                eps=self.rms_norm_eps, init_std=std,
                recompute=self.recompute))
        return (b.layer(CausalLMOutputLayer(
                    name="lm_head", n_out=self.vocab_size,
                    eps=self.rms_norm_eps, init_std=std))
                .set_input_type(InputType.recurrent(1, 2 * self.seq_len))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class NemotronH(ZooModel):
    """Hybrid Mamba-2 / attention / mixture-of-experts causal language
    model of the Nemotron-H family, as Nemotron 3 Nano (published config:
    huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
    ``config.json``, ``model_type: nemotron_h``; arXiv:2504.03624; the
    Mamba-2 layer is arXiv:2405.21060, the router DeepSeek-V3's,
    arXiv:2412.19437; the field names below are the config's keys). **One
    mixer a layer**: ``x <- x + Mixer(RMSNorm(x))`` for each character of
    ``hybrid_override_pattern``, ``M`` a Mamba-2 layer, ``*`` a causal
    grouped-query attention with no positional encoding, no q/k norm and
    no gate, ``E`` the expert layer: a sigmoid router whose choice adds a
    score-correction bias, the chosen experts' unbiased scores
    renormalised and times ``routed_scaling_factor``, non-gated experts
    ``W_down relu(W_up x)^2`` and one shared expert of the same form added
    ungated. RMSNorm (zero-centred), an untied head, next-token loss.

    ``held_experts`` (default: all) and a sliced ``vocab_size`` are an
    expert-parallel deployment's share, as ``Qwen3Next``'s.
    ``bias_update_rate`` is the speed at which each step's load moves the
    routers' bias (the config carries the buffer, not the rate: DeepSeek-
    V3's 1e-3); ``router_aux_loss_coef`` above 0 adds that many times each
    expert layer's balance loss on the normalised sigmoid scores
    (DeepSeek-V3 eq. 17-20) to the training loss. Features are integer
    token ids (N, seq_len), labels
    ``nn.layers.decoder.next_token_labels(ids)``. Not in the model:
    dropout, ``time_step_limit`` (absent from the config: ``dt`` is not
    clamped), ``rescale_prenorm_residual`` (every matrix starts normal(0,
    ``initializer_range``)), generation."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 128
    held_experts: Tuple[int, ...] = ()
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    bias_update_rate: float = 1e-3
    router_aux_loss_coef: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    seq_len: int = 8192
    recompute: bool = True
    compute_dtype: str = "bfloat16"
    updater: Optional[Updater] = None
    seed: int = 123

    def conf(self):
        from deeplearning4j_tpu.nn.layers.decoder import (
            CAUSAL_ATTENTION, EXPERTS, MAMBA2, CausalLMOutputLayer,
            SingleMixerBlock, TokenEmbedding)
        kinds = {"M": MAMBA2, "*": CAUSAL_ATTENTION, "E": EXPERTS}
        unknown = set(self.hybrid_override_pattern) - set(kinds)
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern holds {sorted(unknown)}; a layer "
                "is 'M' (Mamba-2), '*' (attention) or 'E' (experts)")
        std = self.initializer_range
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.updater or Adam(1e-4))
             .compute_dtype(self.compute_dtype)
             .list()
             .layer(TokenEmbedding(name="embed", vocab_size=self.vocab_size,
                                   n_out=self.hidden_size, init_std=std)))
        for l, kind in enumerate(self.hybrid_override_pattern):
            b = b.layer(SingleMixerBlock(
                name=f"block{l}", n_out=self.hidden_size, mixer=kinds[kind],
                n_heads=self.num_attention_heads,
                n_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                mamba_heads=self.mamba_num_heads,
                mamba_head_dim=self.mamba_head_dim, n_groups=self.n_groups,
                d_state=self.ssm_state_size, conv_kernel=self.conv_kernel,
                chunk_size=self.chunk_size, dt_min=self.time_step_min,
                dt_max=self.time_step_max, dt_floor=self.time_step_floor,
                num_experts=self.n_routed_experts,
                held_experts=tuple(self.held_experts),
                expert_hidden=self.moe_intermediate_size,
                shared_hidden=self.moe_shared_expert_intermediate_size,
                top_k=self.num_experts_per_tok,
                norm_topk=self.norm_topk_prob,
                routed_scale=self.routed_scaling_factor,
                bias_update_rate=self.bias_update_rate,
                router_aux_loss_coef=self.router_aux_loss_coef,
                eps=self.layer_norm_epsilon, init_std=std,
                recompute=self.recompute))
        return (b.layer(CausalLMOutputLayer(
                    name="lm_head", n_out=self.vocab_size,
                    eps=self.layer_norm_epsilon, init_std=std))
                .set_input_type(InputType.recurrent(1, self.seq_len))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class AFMoE(ZooModel):
    """Mixture-of-experts causal language model of Arcee's AFMoE family, as
    Trinity-Mini (published config: huggingface.co/arcee-ai/Trinity-Mini,
    ``config.json``, ``model_type: afmoe``; the field names below are its
    keys). Every layer is ``h = x + N2(Attn(N1(x)))``, ``y = h +
    N4(FFN(N3(h)))`` (``nn.layers.decoder.SandwichDecoderBlock``): the
    attention is grouped-query with a per-head q/k RMSNorm and a sigmoid
    output gate, over a causal window of ``sliding_window`` with rotary
    positions on the whole head where ``layer_types[l]`` is
    ``sliding_attention`` and over the whole past with no positional
    encoding where it is ``full_attention``; the feed-forward branch is a
    dense SwiGLU MLP of ``intermediate_size`` for the first
    ``num_dense_layers`` layers and after them gated experts of
    ``moe_intermediate_size`` under a sigmoid router whose choice adds a
    bias that the load moves (``load_balance_coeff`` a step), the chosen
    scores renormalised and times ``route_scale``, with one shared expert
    of the same width added ungated (the family's ``score_func`` sigmoid,
    ``route_norm`` and ``num_shared_experts`` 1). With ``mup_enabled`` the
    embedding's output is multiplied by ``sqrt(hidden_size)``. RMSNorm
    (zero-centred), an untied head, next-token loss.

    ``held_experts`` (default: all) and a sliced ``vocab_size`` are an
    expert-parallel deployment's share, as ``Qwen3Next``'s;
    ``layer_types`` gives the layers built, one each.
    ``router_aux_loss_coef`` above 0 adds that many times each expert
    layer's balance loss on the normalised sigmoid scores to the training
    loss. Features are integer token ids (N, seq_len), labels
    ``nn.layers.decoder.next_token_labels(ids)``. Not in the model:
    dropout, generation."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    layer_types: Tuple[str, ...] = (
        ("sliding_attention",) * 3 + ("full_attention",)) * 8
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 1e4
    num_experts: int = 128
    held_experts: Tuple[int, ...] = ()
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3
    router_aux_loss_coef: float = 0.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    initializer_range: float = 0.02
    seq_len: int = 8192
    recompute: bool = True
    compute_dtype: str = "bfloat16"
    updater: Optional[Updater] = None
    seed: int = 123

    def conf(self):
        from deeplearning4j_tpu.nn.layers.decoder import (
            DENSE, EXPERTS, CausalLMOutputLayer, SandwichDecoderBlock,
            ScaledTokenEmbedding, TokenEmbedding)
        kinds = {"sliding_attention": True, "full_attention": False}
        unknown = set(self.layer_types) - set(kinds)
        if unknown:
            raise ValueError(
                f"layer_types holds {sorted(unknown)}; a layer is "
                "'sliding_attention' or 'full_attention'")
        std = self.initializer_range
        embed = ScaledTokenEmbedding if self.mup_enabled else TokenEmbedding
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.updater or Adam(1e-4))
             .compute_dtype(self.compute_dtype)
             .list()
             .layer(embed(name="embed", vocab_size=self.vocab_size,
                          n_out=self.hidden_size, init_std=std)))
        for l, kind in enumerate(self.layer_types):
            b = b.layer(SandwichDecoderBlock(
                name=f"block{l}", n_out=self.hidden_size,
                ffn=DENSE if l < self.num_dense_layers else EXPERTS,
                n_heads=self.num_attention_heads,
                n_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                window=self.sliding_window if kinds[kind] else None,
                rope_theta=self.rope_theta,
                mlp_hidden=self.intermediate_size,
                num_experts=self.num_experts,
                held_experts=tuple(self.held_experts),
                expert_hidden=self.moe_intermediate_size,
                shared_hidden=self.moe_intermediate_size,
                top_k=self.num_experts_per_tok,
                routed_scale=self.route_scale,
                bias_update_rate=self.load_balance_coeff,
                router_aux_loss_coef=self.router_aux_loss_coef,
                eps=self.rms_norm_eps, init_std=std,
                recompute=self.recompute))
        return (b.layer(CausalLMOutputLayer(
                    name="lm_head", n_out=self.vocab_size,
                    eps=self.rms_norm_eps, init_std=std))
                .set_input_type(InputType.recurrent(1, self.seq_len))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class GLM4MoeLite(ZooModel):
    """Mixture-of-experts causal language model of the GLM-4.7-Flash family
    (published config: huggingface.co/zai-org/GLM-4.7-Flash,
    ``config.json``, ``model_type: glm4_moe_lite``; the field names below
    are its keys). Every layer is ``h = x + MLA(RMSNorm(x))``, ``y = h +
    FFN(RMSNorm(h))`` (``nn.layers.decoder.LatentDecoderBlock``): multi-head
    latent attention with a normed query latent of ``q_lora_rank``, a
    normed key/value latent of ``kv_lora_rank`` and one rotary key of
    ``qk_rope_head_dim`` shared by the ``num_attention_heads`` heads (query
    and key ``qk_nope_head_dim + qk_rope_head_dim``, value ``v_head_dim``);
    the feed-forward branch is a dense SwiGLU MLP of ``intermediate_size``
    for the first ``first_k_dense_replace`` layers and after them gated
    experts of ``moe_intermediate_size`` under a sigmoid router whose
    choice adds a bias that the load moves (``bias_update_rate`` a step;
    ``topk_method`` noaux_tc, one group), the ``num_experts_per_tok``
    chosen scores renormalised (``norm_topk_prob``) and times
    ``routed_scaling_factor``, with ``n_shared_experts`` shared experts of
    the same width added ungated. RMSNorm (zero-centred), an untied head.

    The model also trains the family's multi-token-prediction module
    (``num_nextn_predict_layers`` 1, the release's and the one value
    built; ``MultiTokenPredictionBlock``: one more expert layer on the
    normed final state and the next token's embedding) through the same
    head on the token after the next, its cross-entropy weighted
    ``mtp_loss_weight`` in the loss. The model is a
    ``ComputationGraph``: the final norm is a layer of its own (``norm``)
    that the head and the module both read, the embedding's output
    reaches the module (``embed``) and the module's the head (``mtp``).

    ``held_experts`` (default: all) and a sliced ``vocab_size`` are an
    expert-parallel deployment's share, as ``Qwen3Next``'s.
    ``router_aux_loss_coef`` above 0 adds that many times each expert
    layer's balance loss on the normalised sigmoid scores to the training
    loss. Features are integer token ids (N, seq_len), labels
    ``nn.layers.decoder.next_token_labels(ids)``. Not in the model:
    dropout, generation (the latent cache, the module as a draft)."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    first_k_dense_replace: int = 1
    n_routed_experts: int = 64
    held_experts: Tuple[int, ...] = ()
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    n_shared_experts: int = 1
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    bias_update_rate: float = 1e-3
    router_aux_loss_coef: float = 0.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    seq_len: int = 8192
    recompute: bool = True
    compute_dtype: str = "bfloat16"
    updater: Optional[Updater] = None
    seed: int = 123

    def _block(self, kind, ffn: str):
        return kind(
            n_out=self.hidden_size, ffn=ffn,
            n_heads=self.num_attention_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            mlp_hidden=self.intermediate_size,
            num_experts=self.n_routed_experts,
            held_experts=tuple(self.held_experts),
            expert_hidden=self.moe_intermediate_size,
            shared_hidden=self.n_shared_experts * self.moe_intermediate_size,
            top_k=self.num_experts_per_tok,
            routed_scale=self.routed_scaling_factor,
            bias_update_rate=self.bias_update_rate,
            router_aux_loss_coef=self.router_aux_loss_coef,
            eps=self.rms_norm_eps, init_std=self.initializer_range,
            recompute=self.recompute)

    def conf(self):
        from deeplearning4j_tpu.nn.layers.decoder import (
            DENSE, EXPERTS, LatentDecoderBlock, MultiTokenLMOutputLayer, MultiTokenPredictionBlock,
            TokenEmbedding)
        from deeplearning4j_tpu.nn.layers.normalization import RMSNorm
        if self.num_nextn_predict_layers != 1:
            raise ValueError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}: "
                "the release's 1 is the one built")
        std = self.initializer_range
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.updater or Adam(1e-4))
             .compute_dtype(self.compute_dtype)
             .graph_builder()
             .add_inputs("ids")
             .set_input_types(InputType.recurrent(1, self.seq_len)))
        g.add_layer("embed", TokenEmbedding(
            vocab_size=self.vocab_size, n_out=self.hidden_size,
            init_std=std), "ids")
        last = "embed"
        for l in range(self.num_hidden_layers):
            ffn = DENSE if l < self.first_k_dense_replace else EXPERTS
            g.add_layer(f"layer{l}", self._block(LatentDecoderBlock, ffn),
                        last)
            last = f"layer{l}"
        g.add_layer("norm", RMSNorm(eps=self.rms_norm_eps), last)
        g.add_layer("mtp", self._block(MultiTokenPredictionBlock, EXPERTS),
                    "norm", "embed")
        g.add_layer("lm_head", MultiTokenLMOutputLayer(
            n_out=self.vocab_size, init_std=std,
            mtp_weight=self.mtp_loss_weight), "norm", "mtp")
        return g.set_outputs("lm_head").build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


@dataclasses.dataclass
class Phi4MiniFlash(ZooModel):
    """Decoder-hybrid-decoder causal language model of the Phi-4-mini-flash
    family (published config: huggingface.co/microsoft/
    Phi-4-mini-flash-reasoning, ``config.json``; arXiv:2507.06607; the
    field names below are its keys, the ``mamba_*`` ones the family's
    Mamba-1 sizes). Every block is ``x + Mixer(LayerNorm(x))`` and ``x +
    MLP(LayerNorm(x))`` with a dense gated MLP and no positional encoding;
    the mixer by the block's index ``l`` of ``num_hidden_layers`` (half =
    ``num_hidden_layers // 2``):

    - ``l`` < half, even: Mamba; odd: differential attention over a causal
      window of ``sliding_window``;
    - ``l`` = half: Mamba that also emits its memory (the scan's result
      before the gate); ``l`` = half + 1: differential attention over the
      whole sequence, which also emits its keys and values;
    - above, even: a gated memory unit on that memory; odd: differential
      cross-attention, its own queries on those keys and values.

    The model is a ``ComputationGraph``: what block half and block half +
    1 emit reach their readers as edges (``block16:memory``,
    ``block17:k``), and the head reads the embedding's table
    (``embed:table``, ``tie_word_embeddings``): one matrix, one leaf.

    ``layer_indices`` (default: all) names the published blocks that are
    built, each with its own index (a chip's pipeline stage; differential
    attention's ``lambda_init`` reads the index). ``vocab_size`` may be a
    slice of the published vocabulary: embedding, head and loss are over
    the slice. Features are integer token ids (N, seq_len), labels
    ``nn.layers.decoder.next_token_labels(ids)``."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0          # 0: ceil(hidden_size / 16)
    layer_indices: Tuple[int, ...] = ()
    initializer_range: float = 0.02
    seq_len: int = 8192
    recompute: bool = True
    compute_dtype: str = "bfloat16"
    updater: Optional[Updater] = None
    seed: int = 123

    def mixer_of(self, l: int):
        """``(mixer, emit, window)`` of published block ``l``."""
        from deeplearning4j_tpu.nn.layers.decoder import (
            ATTENTION, CROSS_ATTENTION, GATED_MEMORY, MAMBA)
        half = self.num_hidden_layers // 2
        state_space = l % self.mb_per_layer == 0
        if l < half:
            return ((MAMBA, False, None) if state_space
                    else (ATTENTION, False, self.sliding_window))
        if l < half + 2:
            return (MAMBA if state_space else ATTENTION), True, None
        return (GATED_MEMORY if state_space else CROSS_ATTENTION), False, \
            None

    def conf(self):
        from deeplearning4j_tpu.nn.layers.decoder import (
            CausalLMOutputLayer, StateSpaceHybridBlock, TokenEmbedding)
        std = self.initializer_range
        width = self.hidden_size
        tied = self.tie_word_embeddings
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.updater or Adam(1e-4))
             .compute_dtype(self.compute_dtype)
             .graph_builder()
             .add_inputs("ids")
             .set_input_types(InputType.recurrent(1, self.seq_len)))
        g.add_layer("embed", TokenEmbedding(
            vocab_size=self.vocab_size, n_out=width, init_std=std,
            emit_table=tied), "ids")
        last = "embed"
        emitted = {}                    # extra input -> the source it names
        for l in (self.layer_indices
                  or tuple(range(self.num_hidden_layers))):
            mixer, emit, window = self.mixer_of(l)
            block = StateSpaceHybridBlock(
                n_out=width, mixer=mixer, emit=emit, layer_index=l,
                n_heads=self.num_attention_heads,
                n_kv_heads=self.num_key_value_heads,
                head_dim=width // self.num_attention_heads, window=window,
                d_inner=self.mamba_expand * width,
                d_state=self.mamba_d_state, d_conv=self.mamba_d_conv,
                dt_rank=self.mamba_dt_rank or -(-width // 16),
                mlp_hidden=self.intermediate_size,
                eps=self.layer_norm_eps, init_std=std,
                recompute=self.recompute)
            missing = [e for e in block.extra_inputs if e not in emitted]
            if missing:
                raise ValueError(
                    f"block {l} ({mixer}) reads {missing}, which no block "
                    f"of layer_indices={self.layer_indices} before it "
                    "emits")
            g.add_layer(f"block{l}", block, last,
                        *(emitted[e] for e in block.extra_inputs))
            last = f"block{l}"
            for extra in block.extra_output_types(None):
                emitted[extra] = f"{last}:{extra}"
        g.add_layer("lm_head", CausalLMOutputLayer(
            n_out=self.vocab_size, eps=self.layer_norm_eps, init_std=std,
            norm="layer", tied=tied),
            last, *(("embed:table",) if tied else ()))
        return g.set_outputs("lm_head").build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


@dataclasses.dataclass
class AlexNet(ZooModel):
    """reference: model/AlexNet.java (single-stream variant)."""
    num_classes: int = 1000
    height: int = 224
    width: int = 224
    channels: int = 3
    seed: int = 123

    def conf(self):
        return (NeuralNetConfiguration.Builder()
                .seed(self.seed)
                .updater(Nesterovs(1e-2, 0.9))
                .list()
                .layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11),
                                        stride=(4, 4),
                                        activation=Activation.RELU))
                .layer(SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5),
                                        convolution_mode=ConvolutionMode.SAME,
                                        activation=Activation.RELU))
                .layer(SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                        convolution_mode=ConvolutionMode.SAME,
                                        activation=Activation.RELU))
                .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                        convolution_mode=ConvolutionMode.SAME,
                                        activation=Activation.RELU))
                .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3),
                                        convolution_mode=ConvolutionMode.SAME,
                                        activation=Activation.RELU))
                .layer(SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
                .layer(DenseLayer(n_out=4096, activation=Activation.RELU,
                                  dropout=0.5))
                .layer(DenseLayer(n_out=4096, activation=Activation.RELU,
                                  dropout=0.5))
                .layer(OutputLayer(n_out=self.num_classes))
                .set_input_type(InputType.convolutional(
                    self.height, self.width, self.channels))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class VGG19(ZooModel):
    """reference: model/VGG19.java — VGG16 with the deeper [2,2,4,4,4]
    conv plan."""
    num_classes: int = 1000
    height: int = 224
    width: int = 224
    channels: int = 3
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Nesterovs(1e-2, 0.9))
             .compute_dtype(self.compute_dtype)
             .list())
        plan = [(64, 2), (128, 2), (256, 4), (512, 4), (512, 4)]
        for n_out, reps in plan:
            for _ in range(reps):
                b = b.layer(ConvolutionLayer(
                    n_out=n_out, kernel_size=(3, 3),
                    convolution_mode=ConvolutionMode.SAME,
                    activation=Activation.RELU))
            b = b.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        return (b.layer(DenseLayer(n_out=4096, activation=Activation.RELU,
                                   dropout=0.5))
                .layer(DenseLayer(n_out=4096, activation=Activation.RELU,
                                  dropout=0.5))
                .layer(OutputLayer(n_out=self.num_classes))
                .set_input_type(InputType.convolutional(
                    self.height, self.width, self.channels))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


def _darknet_block(b, n_out, kernel):
    """conv + BN + leaky-relu (reference: model/helper/DarknetHelper.java
    addLayers — conv/BN/LeakyReLU triple)."""
    return (b.layer(ConvolutionLayer(
                n_out=n_out, kernel_size=kernel,
                convolution_mode=ConvolutionMode.SAME, has_bias=False,
                activation=Activation.IDENTITY))
            .layer(BatchNormalization())
            .layer(ActivationLayer(activation=Activation.LEAKYRELU)))


@dataclasses.dataclass
class Darknet19(ZooModel):
    """reference: model/Darknet19.java — the YOLO2 classification
    backbone (19 convs, 1x1 bottlenecks between 3x3s)."""
    num_classes: int = 1000
    height: int = 224
    width: int = 224
    channels: int = 3
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Nesterovs(1e-3, 0.9))
             .compute_dtype(self.compute_dtype)
             .list())
        b = _darknet_block(b, 32, (3, 3))
        b = b.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        b = _darknet_block(b, 64, (3, 3))
        b = b.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        for mid, outer in ((64, 128), (128, 256)):
            b = _darknet_block(b, outer, (3, 3))
            b = _darknet_block(b, mid, (1, 1))
            b = _darknet_block(b, outer, (3, 3))
            b = b.layer(SubsamplingLayer(kernel_size=(2, 2),
                                         stride=(2, 2)))
        for mid, outer in ((256, 512), (512, 1024)):
            b = _darknet_block(b, outer, (3, 3))
            b = _darknet_block(b, mid, (1, 1))
            b = _darknet_block(b, outer, (3, 3))
            b = _darknet_block(b, mid, (1, 1))
            b = _darknet_block(b, outer, (3, 3))
            if outer == 512:
                b = b.layer(SubsamplingLayer(kernel_size=(2, 2),
                                             stride=(2, 2)))
        b = b.layer(ConvolutionLayer(n_out=self.num_classes,
                                     kernel_size=(1, 1),
                                     convolution_mode=ConvolutionMode.SAME,
                                     activation=Activation.IDENTITY))
        return (b.layer(GlobalPoolingLayer(pooling_type=PoolingType.AVG))
                .layer(LossLayer(loss=LossFunction.MCXENT,
                                 activation=Activation.SOFTMAX))
                .set_input_type(InputType.convolutional(
                    self.height, self.width, self.channels))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class TinyYOLO(ZooModel):
    """reference: model/TinyYOLO.java — tiny-YOLOv2 detector: 6 darknet
    conv/pool stages then a 1x1 head into Yolo2OutputLayer. Default
    anchors are the reference's (in 13x13-grid units)."""
    num_classes: int = 20
    height: int = 416
    width: int = 416
    channels: int = 3
    boxes: Tuple = ((1.08, 1.19), (3.42, 4.41), (6.63, 11.38),
                    (9.42, 5.11), (16.62, 10.52))
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Adam(1e-3))
             .compute_dtype(self.compute_dtype)
             .list())
        for n_out in (16, 32, 64, 128, 256):
            b = _darknet_block(b, n_out, (3, 3))
            b = b.layer(SubsamplingLayer(kernel_size=(2, 2),
                                         stride=(2, 2)))
        b = _darknet_block(b, 512, (3, 3))
        b = b.layer(SubsamplingLayer(
            kernel_size=(2, 2), stride=(1, 1),
            convolution_mode=ConvolutionMode.SAME))
        b = _darknet_block(b, 1024, (3, 3))
        b = _darknet_block(b, 1024, (3, 3))
        n_b = len(self.boxes)
        b = b.layer(ConvolutionLayer(
            n_out=n_b * (5 + self.num_classes), kernel_size=(1, 1),
            convolution_mode=ConvolutionMode.SAME,
            activation=Activation.IDENTITY))
        return (b.layer(Yolo2OutputLayer(boxes=self.boxes))
                .set_input_type(InputType.convolutional(
                    self.height, self.width, self.channels))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


@dataclasses.dataclass
class YOLO2(ZooModel):
    """reference: model/YOLO2.java — Darknet19 backbone + passthrough:
    the 512-channel stage-5 map rides a SpaceToDepth into the head merge
    (reference uses a route/reorg pair; here MergeVertex + SpaceToDepth)."""
    num_classes: int = 20
    height: int = 416
    width: int = 416
    channels: int = 3
    boxes: Tuple = ((0.57273, 0.677385), (1.87446, 2.06253),
                    (3.33843, 5.47434), (7.88282, 3.52778),
                    (9.77052, 9.16828))
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        from deeplearning4j_tpu.nn.graph.vertices import MergeVertex
        from deeplearning4j_tpu.nn.layers.convolution import (
            SpaceToDepthLayer)
        from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Adam(1e-3))
             .compute_dtype(self.compute_dtype)
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def block(name, src, n_out, kernel):
            g.add_layer(f"{name}_conv", ConvolutionLayer(
                n_out=n_out, kernel_size=kernel,
                convolution_mode=ConvolutionMode.SAME, has_bias=False,
                activation=Activation.IDENTITY), src)
            g.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_conv")
            g.add_layer(f"{name}_act",
                        ActivationLayer(activation=Activation.LEAKYRELU),
                        f"{name}_bn")
            return f"{name}_act"

        def pool(name, src):
            g.add_layer(name, SubsamplingLayer(kernel_size=(2, 2),
                                               stride=(2, 2)), src)
            return name

        x = block("c1", "in", 32, (3, 3))
        x = pool("p1", x)
        x = block("c2", x, 64, (3, 3))
        x = pool("p2", x)
        for i, (mid, outer) in enumerate(((64, 128), (128, 256))):
            x = block(f"s{i}a", x, outer, (3, 3))
            x = block(f"s{i}b", x, mid, (1, 1))
            x = block(f"s{i}c", x, outer, (3, 3))
            x = pool(f"s{i}p", x)
        # stage 5 (512): its output is the passthrough source
        x = block("s2a", x, 512, (3, 3))
        x = block("s2b", x, 256, (1, 1))
        x = block("s2c", x, 512, (3, 3))
        x = block("s2d", x, 256, (1, 1))
        passthrough = block("s2e", x, 512, (3, 3))
        x = pool("s2p", passthrough)
        # stage 6 (1024)
        x = block("s3a", x, 1024, (3, 3))
        x = block("s3b", x, 512, (1, 1))
        x = block("s3c", x, 1024, (3, 3))
        x = block("s3d", x, 512, (1, 1))
        x = block("s3e", x, 1024, (3, 3))
        # head
        x = block("h1", x, 1024, (3, 3))
        x = block("h2", x, 1024, (3, 3))
        g.add_layer("reorg", SpaceToDepthLayer(block_size=2), passthrough)
        g.add_vertex("cat", MergeVertex(), "reorg", "h2_act")
        x = block("h3", "cat", 1024, (3, 3))
        n_b = len(self.boxes)
        g.add_layer("head", ConvolutionLayer(
            n_out=n_b * (5 + self.num_classes), kernel_size=(1, 1),
            convolution_mode=ConvolutionMode.SAME,
            activation=Activation.IDENTITY), x)
        g.add_layer("yolo", Yolo2OutputLayer(boxes=self.boxes), "head")
        g.set_outputs("yolo")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


@dataclasses.dataclass
class GoogLeNet(ZooModel):
    """reference: model/GoogLeNet.java — Inception-v1: stem + 9 inception
    modules (4-branch MergeVertex each) + avg-pool head."""
    num_classes: int = 1000
    height: int = 224
    width: int = 224
    channels: int = 3
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        from deeplearning4j_tpu.nn.graph.vertices import MergeVertex
        from deeplearning4j_tpu.nn.layers.normalization import (
            LocalResponseNormalization)
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Nesterovs(1e-2, 0.9))
             .compute_dtype(self.compute_dtype)
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def conv(name, src, n_out, k, s=(1, 1)):
            g.add_layer(name, ConvolutionLayer(
                n_out=n_out, kernel_size=k, stride=s,
                convolution_mode=ConvolutionMode.SAME,
                activation=Activation.RELU), src)
            return name

        def inception(name, src, c1, c3r, c3, c5r, c5, cp):
            """4 branches: 1x1 / 1x1->3x3 / 1x1->5x5 / pool->1x1
            (reference: GoogLeNet.java inception helper)."""
            b1 = conv(f"{name}_b1", src, c1, (1, 1))
            conv(f"{name}_b3r", src, c3r, (1, 1))
            b3 = conv(f"{name}_b3", f"{name}_b3r", c3, (3, 3))
            conv(f"{name}_b5r", src, c5r, (1, 1))
            b5 = conv(f"{name}_b5", f"{name}_b5r", c5, (5, 5))
            g.add_layer(f"{name}_pool", SubsamplingLayer(
                kernel_size=(3, 3), stride=(1, 1),
                convolution_mode=ConvolutionMode.SAME), src)
            bp = conv(f"{name}_bp", f"{name}_pool", cp, (1, 1))
            g.add_vertex(name, MergeVertex(), b1, b3, b5, bp)
            return name

        x = conv("conv1", "in", 64, (7, 7), (2, 2))
        g.add_layer("pool1", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        g.add_layer("lrn1", LocalResponseNormalization(), "pool1")
        x = conv("conv2r", "lrn1", 64, (1, 1))
        x = conv("conv2", x, 192, (3, 3))
        g.add_layer("lrn2", LocalResponseNormalization(), x)
        g.add_layer("pool2", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), "lrn2")
        x = inception("i3a", "pool2", 64, 96, 128, 16, 32, 32)
        x = inception("i3b", x, 128, 128, 192, 32, 96, 64)
        g.add_layer("pool3", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        x = inception("i4a", "pool3", 192, 96, 208, 16, 48, 64)
        x = inception("i4b", x, 160, 112, 224, 24, 64, 64)
        x = inception("i4c", x, 128, 128, 256, 24, 64, 64)
        x = inception("i4d", x, 112, 144, 288, 32, 64, 64)
        x = inception("i4e", x, 256, 160, 320, 32, 128, 128)
        g.add_layer("pool4", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        x = inception("i5a", "pool4", 256, 160, 320, 32, 128, 128)
        x = inception("i5b", x, 384, 192, 384, 48, 128, 128)
        g.add_layer("avgpool", GlobalPoolingLayer(
            pooling_type=PoolingType.AVG), x)
        g.add_layer("drop", DropoutLayer(dropout=0.4), "avgpool")
        g.add_layer("out", OutputLayer(n_out=self.num_classes,
                                       loss=LossFunction.MCXENT,
                                       activation=Activation.SOFTMAX),
                    "drop")
        g.set_outputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


@dataclasses.dataclass
class InceptionResNetV1(ZooModel):
    """reference: model/InceptionResNetV1.java (+ helper/
    InceptionResNetHelper.java) — FaceNet-style embedding net: stem,
    5x block35, reduction-A, 10x block17, reduction-B, 5x block8,
    128-d L2-normalized embedding, center-loss softmax head."""
    num_classes: int = 1001
    embedding_size: int = 128
    height: int = 160
    width: int = 160
    channels: int = 3
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        from deeplearning4j_tpu.nn.graph.vertices import (
            ElementWiseVertex, L2NormalizeVertex, MergeVertex, ScaleVertex)
        from deeplearning4j_tpu.nn.layers.output import (
            CenterLossOutputLayer)
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Adam(1e-3))
             .compute_dtype(self.compute_dtype)
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def conv(name, src, n_out, k, s=(1, 1), act=Activation.RELU):
            g.add_layer(f"{name}_c", ConvolutionLayer(
                n_out=n_out, kernel_size=k, stride=s,
                convolution_mode=ConvolutionMode.SAME, has_bias=False,
                activation=Activation.IDENTITY), src)
            g.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_c")
            if act is None:
                return f"{name}_bn"
            g.add_layer(f"{name}_a", ActivationLayer(activation=act),
                        f"{name}_bn")
            return f"{name}_a"

        def residual(name, src, branches, n_channels, scale):
            """merge(branches) -> linear 1x1 up-projection -> scaled
            residual add -> relu (InceptionResNetHelper block pattern)."""
            g.add_vertex(f"{name}_cat", MergeVertex(), *branches)
            up = conv(f"{name}_up", f"{name}_cat", n_channels, (1, 1),
                      act=None)
            g.add_vertex(f"{name}_scale", ScaleVertex(scale=scale), up)
            g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), src,
                         f"{name}_scale")
            g.add_layer(f"{name}_out",
                        ActivationLayer(activation=Activation.RELU),
                        f"{name}_add")
            return f"{name}_out"

        def block35(name, src):
            b1 = conv(f"{name}_b1", src, 32, (1, 1))
            b2 = conv(f"{name}_b2b", conv(f"{name}_b2a", src, 32, (1, 1)),
                      32, (3, 3))
            b3 = conv(f"{name}_b3c",
                      conv(f"{name}_b3b",
                           conv(f"{name}_b3a", src, 32, (1, 1)), 32,
                           (3, 3)), 32, (3, 3))
            return residual(name, src, (b1, b2, b3), 256, 0.17)

        def block17(name, src):
            b1 = conv(f"{name}_b1", src, 128, (1, 1))
            b2 = conv(f"{name}_b2c",
                      conv(f"{name}_b2b",
                           conv(f"{name}_b2a", src, 128, (1, 1)), 128,
                           (1, 7)), 128, (7, 1))
            return residual(name, src, (b1, b2), 896, 0.10)

        def block8(name, src):
            b1 = conv(f"{name}_b1", src, 192, (1, 1))
            b2 = conv(f"{name}_b2c",
                      conv(f"{name}_b2b",
                           conv(f"{name}_b2a", src, 192, (1, 1)), 192,
                           (1, 3)), 192, (3, 1))
            return residual(name, src, (b1, b2), 1792, 0.20)

        # stem
        x = conv("stem1", "in", 32, (3, 3), (2, 2))
        x = conv("stem2", x, 32, (3, 3))
        x = conv("stem3", x, 64, (3, 3))
        g.add_layer("stem_pool", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        x = conv("stem4", "stem_pool", 80, (1, 1))
        x = conv("stem5", x, 192, (3, 3))
        x = conv("stem6", x, 256, (3, 3), (2, 2))
        for i in range(5):
            x = block35(f"b35_{i}", x)
        # reduction-A -> 896 channels
        g.add_layer("redA_pool", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        ra1 = conv("redA_b1", x, 384, (3, 3), (2, 2))
        ra2 = conv("redA_b2c",
                   conv("redA_b2b", conv("redA_b2a", x, 192, (1, 1)),
                        192, (3, 3)), 256, (3, 3), (2, 2))
        g.add_vertex("redA", MergeVertex(), "redA_pool", ra1, ra2)
        x = "redA"
        for i in range(10):
            x = block17(f"b17_{i}", x)
        # reduction-B -> 1792 channels
        g.add_layer("redB_pool", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        rb1 = conv("redB_b1b", conv("redB_b1a", x, 256, (1, 1)), 384,
                   (3, 3), (2, 2))
        rb2 = conv("redB_b2b", conv("redB_b2a", x, 256, (1, 1)), 256,
                   (3, 3), (2, 2))
        rb3 = conv("redB_b3c",
                   conv("redB_b3b", conv("redB_b3a", x, 256, (1, 1)),
                        256, (3, 3)), 256, (3, 3), (2, 2))
        g.add_vertex("redB", MergeVertex(), "redB_pool", rb1, rb2, rb3)
        x = "redB"
        for i in range(5):
            x = block8(f"b8_{i}", x)
        g.add_layer("avgpool", GlobalPoolingLayer(
            pooling_type=PoolingType.AVG), x)
        g.add_layer("bottleneck", DenseLayer(
            n_out=self.embedding_size, activation=Activation.IDENTITY),
            "avgpool")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("out", CenterLossOutputLayer(
            n_out=self.num_classes, loss=LossFunction.MCXENT,
            activation=Activation.SOFTMAX), "embeddings")
        g.set_outputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


@dataclasses.dataclass
class FaceNetNN4Small2(ZooModel):
    """reference: model/FaceNetNN4Small2.java (+ helper/FaceNetHelper.java)
    — the NN4-small2 GoogLeNet-style face embedding net: stem, mixed
    3a/3b/3c/4a/4e/5a/5b inception blocks, 128-d L2-normalized embedding,
    center-loss softmax head."""
    num_classes: int = 5749
    embedding_size: int = 128
    height: int = 96
    width: int = 96
    channels: int = 3
    seed: int = 123
    compute_dtype: str = "float32"

    def conf(self):
        from deeplearning4j_tpu.nn.graph.vertices import (
            L2NormalizeVertex, MergeVertex)
        from deeplearning4j_tpu.nn.layers.normalization import (
            LocalResponseNormalization)
        from deeplearning4j_tpu.nn.layers.output import (
            CenterLossOutputLayer)
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(Adam(1e-3))
             .compute_dtype(self.compute_dtype)
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def conv(name, src, n_out, k, s=(1, 1)):
            g.add_layer(f"{name}_c", ConvolutionLayer(
                n_out=n_out, kernel_size=k, stride=s,
                convolution_mode=ConvolutionMode.SAME,
                activation=Activation.IDENTITY), src)
            g.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_c")
            g.add_layer(f"{name}_a",
                        ActivationLayer(activation=Activation.RELU),
                        f"{name}_bn")
            return f"{name}_a"

        def inception(name, src, c3r, c3, c5r, c5, cp, c1,
                      strided=False):
            """FaceNetHelper.appendGraph-style mixed block; ``strided``
            blocks (3c, 4e) drop the 1x1 branch and downsample."""
            stride = (2, 2) if strided else (1, 1)
            branches = []
            b3 = conv(f"{name}_3", conv(f"{name}_3r", src, c3r, (1, 1)),
                      c3, (3, 3), stride)
            branches.append(b3)
            if c5:
                b5 = conv(f"{name}_5",
                          conv(f"{name}_5r", src, c5r, (1, 1)), c5,
                          (5, 5), stride)
                branches.append(b5)
            g.add_layer(f"{name}_pool", SubsamplingLayer(
                kernel_size=(3, 3),
                stride=(2, 2) if strided else (1, 1),
                convolution_mode=ConvolutionMode.SAME), src)
            if cp:
                branches.append(conv(f"{name}_pp", f"{name}_pool", cp,
                                     (1, 1)))
            else:
                branches.append(f"{name}_pool")
            if c1:
                branches.append(conv(f"{name}_1", src, c1, (1, 1)))
            g.add_vertex(name, MergeVertex(), *branches)
            return name

        x = conv("conv1", "in", 64, (7, 7), (2, 2))
        g.add_layer("pool1", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), x)
        g.add_layer("lrn1", LocalResponseNormalization(), "pool1")
        x = conv("conv2", "lrn1", 64, (1, 1))
        x = conv("conv3", x, 192, (3, 3))
        g.add_layer("lrn2", LocalResponseNormalization(), x)
        g.add_layer("pool2", SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2),
            convolution_mode=ConvolutionMode.SAME), "lrn2")
        x = inception("mixed3a", "pool2", 96, 128, 16, 32, 32, 64)
        x = inception("mixed3b", x, 96, 128, 32, 64, 64, 64)
        x = inception("mixed3c", x, 128, 256, 32, 64, 0, 0, strided=True)
        x = inception("mixed4a", x, 96, 192, 32, 64, 128, 256)
        x = inception("mixed4e", x, 160, 256, 64, 128, 0, 0, strided=True)
        x = inception("mixed5a", x, 96, 384, 0, 0, 96, 256)
        x = inception("mixed5b", x, 96, 384, 0, 0, 96, 256)
        g.add_layer("avgpool", GlobalPoolingLayer(
            pooling_type=PoolingType.AVG), x)
        g.add_layer("bottleneck", DenseLayer(
            n_out=self.embedding_size, activation=Activation.IDENTITY),
            "avgpool")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("out", CenterLossOutputLayer(
            n_out=self.num_classes, loss=LossFunction.MCXENT,
            activation=Activation.SOFTMAX), "embeddings")
        g.set_outputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()
