"""Plain float32 ResNet-50 (He et al., "Deep Residual Learning for Image
Recognition", arXiv:1512.03385, Table 1, the 50-layer column).

Straightforward ``jax.numpy``: no kernels, no fusion, no bfloat16, every
contraction at ``default_matmul_precision("highest")``. It reads the
system's parameter tree (seeded random weights) and nothing else of the
program.

As published: conv1 7x7/2 with 64 filters, 3x3/2 max pool, four stages
of [3, 4, 6, 3] bottlenecks (1x1 f, 3x3 f, 1x1 4f; f = 64, 128, 256,
512) with batch normalisation after every convolution and before the
ReLU, projection shortcuts where the shape changes, the stride of a
stage's first block on its first 1x1 (the paper's and the reference
zoo's placement, not the later "v1.5" on the 3x3), global average pool,
one fully connected layer, softmax.

Departures, each because the system under test is built that way:

- The stem. ``s2d_stem=True`` holds a 4x4 kernel over the
  space-to-depth image (H/2 x W/2 x 12), which is an 8x8 stride-2
  convolution on the 3-channel image padded by (2, 4): the published
  7x7 kernel padded (2, 3) plus one more row and column of taps. With
  weights folded from a 7x7 kernel (``zoo.models.fold_stem_weights``)
  those taps are zero and the two are the same function; seeded random
  weights fill them. This file unfolds the system's kernel to 8x8x3 and
  applies it to the image with a plain strided convolution, so the
  space-to-depth rearrangement itself is what is checked.
- 200 classes and 64x64 inputs (TinyImageNet), not 1000 and 224.
- Batch-norm epsilon 1e-5, biased batch variance, the system's values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
STAGE_BLOCKS = (3, 4, 6, 3)
_DN = ("NHWC", "HWIO", "NHWC")


def _unfold_stem(w4):
    """(4, 4, 4C, O) over space-to-depth blocks -> (8, 8, C, O) over
    pixels: tap (2ku+a, 2kv+b, c) sits in slot (a*2+b)*C + c of block
    (ku, kv), the (row, col, channel) packing of the s2d layer."""
    c = w4.shape[2] // 4
    w = w4.reshape(4, 4, 2, 2, c, w4.shape[3])        # ku kv a b c o
    return w.transpose(0, 2, 1, 3, 4, 5).reshape(8, 8, c, w4.shape[3])


def _conv(x, w, stride=1, padding="SAME"):
    return lax.conv_general_dilated(x, w, (stride, stride), padding,
                                    dimension_numbers=_DN)


def _bn(x, gamma, beta, mean, var):
    return (x - mean) * lax.rsqrt(var + EPS) * gamma + beta


def _walk(params, state, images, train: bool):
    """Logits, and the mean and variance every batch-norm used, laid out
    like the system's ``model_state``."""
    used = {}

    def bn(x, gamma, beta, layer, mean_key, var_key):
        if train:
            mean = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.var(x, axis=(0, 1, 2))
        else:
            mean, var = state[layer][mean_key], state[layer][var_key]
        used.setdefault(layer, {})[mean_key] = mean
        used[layer][var_key] = var
        return _bn(x, gamma, beta, mean, var)

    x = images.astype(jnp.float32)
    x = _conv(x, _unfold_stem(params["conv1_conv"]["W"]), 2,
              ((2, 4), (2, 4)))
    p = params["conv1_bn"]
    x = jax.nn.relu(bn(x, p["gamma"], p["beta"], "conv1_bn", "mean", "var"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for si, blocks in enumerate(STAGE_BLOCKS):
        for bi in range(blocks):
            name = f"s{si}b{bi}"
            p = params[name]
            stride = 2 if bi == 0 and si > 0 else 1

            def cbn(x, w, tag, s=1):
                w = w[None, None] if w.ndim == 2 else w
                return bn(_conv(x, w, s), p[f"{tag}_gamma"],
                          p[f"{tag}_beta"], name, f"{tag}_mean",
                          f"{tag}_var")

            y = jax.nn.relu(cbn(x, p["W1"], "bn1", stride))
            y = jax.nn.relu(cbn(y, p["W2"], "bn2"))
            y = cbn(y, p["W3"], "bn3")
            shortcut = cbn(x, p["Wds"], "bnds", stride) if bi == 0 else x
            x = jax.nn.relu(y + shortcut)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["out"]["W"] + params["out"]["b"], used


@jax.jit
def _loss(params, state, features, labels):
    with jax.default_matmul_precision("highest"):
        logits, _ = _walk(params, state, features[0], train=True)
        return -jnp.mean(
            jnp.sum(labels[0] * jax.nn.log_softmax(logits), -1))


@jax.jit
def _predict(params, state, features):
    with jax.default_matmul_precision("highest"):
        logits, _ = _walk(params, state, features[0], train=False)
        return jax.nn.softmax(logits)


@jax.jit
def _batch_statistics(params, state, features):
    with jax.default_matmul_precision("highest"):
        _, used = _walk(params, state, features[0], train=True)
        return {**state, **used}


# The depth and the widths are the published ones and are read off the
# parameter tree, so ``cfg`` (the configuration file) is not consulted;
# every reference takes it first all the same.

def loss(cfg, params, state, features, labels):
    """Training-mode forward (batch statistics) and the mean
    cross-entropy of the softmax against one-hot labels. ``features``
    and ``labels`` are the one-element tuples of a single-input graph."""
    return _loss(params, state, features, labels)


def predict(cfg, params, state, features):
    """Inference-mode forward (running statistics): class
    probabilities."""
    return _predict(params, state, features)


def batch_statistics(cfg, params, state, features):
    """A ``model_state`` whose running statistics are this batch's: what
    training would converge the running averages to on such data. The
    serving driver installs it, because a ResNet served with the
    initial statistics (mean 0, variance 1) normalises nothing, its
    activations grow through sixteen residual blocks, the softmax
    saturates, and a rounding in bfloat16 then flips whole answers."""
    return _batch_statistics(params, state, features)
