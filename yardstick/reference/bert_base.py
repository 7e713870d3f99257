"""Plain float32 BERT encoder with a mean-pool classification head
(Devlin et al., "BERT", arXiv:1810.04805; sizes from
``google-bert/bert-base-uncased`` ``config.json``).

Straightforward ``jax.numpy``: no kernels, no bfloat16, every contraction
at ``default_matmul_precision("highest")``. It reads the system's
parameter tree (seeded random weights) and nothing else of the program.

As published: learned token and position embeddings summed and layer-
normalised; each layer is multi-head self-attention (softmax of
QK^T / sqrt(d_head)), a residual add and LayerNorm *after* it (post-LN),
then a GELU (exact, erf) feed-forward of four times the width, a
residual add and LayerNorm; LayerNorm epsilon 1e-12.

Departures, each because the graph the system imports
(``modelimport.bert.build_keras_bert``) is built that way; the
configuration file lists them too:

- no token-type (segment) embedding, no pooler (tanh dense on [CLS]) and
  no padding mask: every sequence is ``seq_len`` long;
- the fine-tune head is the mean over positions, one dense layer of two
  classes and softmax, not the pooler's [CLS] vector;
- no dropout (the imported graph has none).

The system packs Q, K and V into one (width, 3*width) matrix whose
columns run (head, which, d_head); this file slices the three out and
computes them apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-12


def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * p["gamma"] + p["beta"]


def _attention(x, p, n_heads):
    n, t, width = x.shape
    dh = width // n_heads
    w = p["Wqkv"].reshape(width, n_heads, 3, dh)
    b = p["bqkv"].reshape(n_heads, 3, dh)
    q, k, v = (jnp.einsum("ntf,fhd->nhtd", x, w[:, :, i]) + b[:, i][:, None]
               for i in range(3))
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / jnp.sqrt(float(dh))
    ctx = jnp.einsum("nhqk,nhkd->nqhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(n, t, width) @ p["Wo"] + p["bo"]


def _logits(params, ids, positions, n_heads):
    x = (params["tok_embed"]["W"][ids.astype(jnp.int32)]
         + params["pos_embed"]["W"][positions.astype(jnp.int32)])
    x = _layer_norm(x, params["embed_ln"])
    n_layers = sum(1 for k in params if k.endswith("_mha"))
    for i in range(n_layers):
        x = _layer_norm(x + _attention(x, params[f"l{i}_mha"], n_heads),
                        params[f"l{i}_ln1"])
        ff = jax.nn.gelu(x @ params[f"l{i}_ff1"]["W"]
                         + params[f"l{i}_ff1"]["b"], approximate=False)
        ff = ff @ params[f"l{i}_ff2"]["W"] + params[f"l{i}_ff2"]["b"]
        x = _layer_norm(x + ff, params[f"l{i}_ln2"])
    pooled = jnp.mean(x, axis=1)
    return pooled @ params["cls"]["W"] + params["cls"]["b"]


@functools.partial(jax.jit, static_argnames="n_heads")
def _loss(params, features, labels, n_heads):
    with jax.default_matmul_precision("highest"):
        logits = _logits(params, features[0], features[1], n_heads)
        return -jnp.mean(
            jnp.sum(labels[0] * jax.nn.log_softmax(logits), -1))


def loss(cfg, params, state, features, labels):
    """Mean cross-entropy of the two-class softmax; ``features`` is
    (token ids, position ids), ``labels`` a one-element tuple. The graph
    keeps no running statistics, so ``state`` is not read."""
    return _loss(params, features, labels, cfg["num_attention_heads"])
