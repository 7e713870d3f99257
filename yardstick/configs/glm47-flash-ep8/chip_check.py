"""The comparison on the chip, at the published widths and the
timed sizes: ``python yardstick/configs/glm47-flash-ep8/chip_check.py
--seeds 21 22`` (a TPU; some minutes a seed). Exit code 1 when a limit
below is passed, or when the float8 control is let through by one.

It is the Nemotron 3 Nano configuration's ``chip_check.py`` (that file's
docstring says what each reading, control and row is; its controls, the
float32 row's tiles and limit are taken from it) with what this model
adds: the module's logits through the shared head beside the next-token
ones, the two cross-entropies beside the balance term the harness
compares, and gradients by kinds of this model's own. For each seed, 2
labelled rows of 8,192 tokens from ``rows_with_labels``:

- ``logits_rms_over_spread`` and ``mtp_logits_rms_over_spread``: root
  mean square of the difference from the float32 reference over the 2
  rows, as a share of the reference logits' own spread, for each head;
- ``loss_rel_err``: what the harness compares and the cell's
  ``loss_tolerance`` limits, the score on ``check_batch`` (these rows:
  both cross-entropies, the module's weighted, and the balance term of
  the four expert layers and the module's);
- ``next_token_loss_rel_err`` and ``mtp_loss_rel_err``: the two
  cross-entropies before their weights (the system's as its head leaves
  them in ``lm_loss_terms``). No limit of their own: a cross-entropy's
  error at seeded weights is a signed mean of rounding errors, which a
  float8 control draws inside any limit on some seeds (PERF.md section
  6); they are read within ``loss``;
- ``gradients`` (the first seed only): on 1 labelled row, ``|g - g_ref| /
  |g_ref|`` parameter by parameter, the worst by kind; the kind is the
  leaf's path within its layer (``['mixer']['W_kvb']``: every layer's and
  the module's alike), but for the embedding, the final norm and the head,
  whose kind is their whole path (``['lm_head']['W']``).

Every limit lies between what the system read and what the float8
control read, and the control has to be over each. One JSON object per
seed, appended to ``chiprun_out/glm4_moe_lite_chip_check.jsonl``. Nothing
here is timed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from yardstick import cells  # noqa: E402

_nemotron = cells.load_file_module(
    Path(__file__).resolve().parents[1] / "nemotron3-nano-30b-a3b-ep16"
    / "chip_check.py")
CONTROLS, MUST_BE_REFUSED, FLOAT32_GRADIENT_LIMIT = (
    _nemotron.CONTROLS, _nemotron.MUST_BE_REFUSED,
    _nemotron.FLOAT32_GRADIENT_LIMIT)

CELL = "glm47-flash-ep8.fit-seq8k"

# Each limit lies between what the bfloat16 system read on the chip and
# what the float8 control read (PERF.md section 6: a TPU v5e, seeds 21
# to 25, gradients on 21). ``loss`` is the configuration's
# ``loss_tolerance``, read from its file (the labelled loss: system at
# most 1.37e-4 over eleven seeds, float8 control 5.6e-2 at least).
LIMITS = {
    # system 1.92-3.05%, reference at bfloat16 operands 1.72-2.89%, float8
    # control 128.2-129.1% (seeds 21-25)
    "logits_rms_over_spread": 0.085,
    # system 1.70-2.77%, bfloat16 operands 1.47-2.47%, float8 96.0-96.5%
    "mtp_logits_rms_over_spread": 0.085,
    # the worst leaf by kind; system / bfloat16 operands / float8 (the
    # loss with the balance term at 0.1); the float32 row 0.32% at most
    "gradient_latent": 0.25,     # 2.0% (q_norm) / 2.0% / 100% (W_o)
    "gradient_w_eh": 0.25,       # 0.99% / 0.91% / 92%
    "gradient_head": 0.25,       # 0.85% / 0.82% / 87%
    "gradient_dense": 0.25,      # 1.3% (shared_gate) / 1.7% / 69% (enorm)
    "gradient_routed": 0.35,     # 7.7% (w_gate) / 9.8% / 100% (w_up)
    "gradient_router": 0.13,     # 1.5% / 2.5% / 99%
}
LATENT_KINDS = tuple(f"['mixer']['{k}']" for k in (
    "W_qa", "q_norm", "W_qb", "W_kva", "kv_norm", "W_kvb", "W_o"))
ROUTED_KINDS = ("['moe']['w_gate']", "['moe']['w_up']", "['moe']['w_down']")
ROUTER_KINDS = ("['moe']['router']",)
WHOLE_PATH = ("embed", "norm", "lm_head")   # groups that are one layer


def gradient_limit(kind: str) -> str:
    """The limit a gradient kind is held to."""
    if kind.endswith(LATENT_KINDS):
        return "gradient_latent"
    if kind == "['W_eh']":
        return "gradient_w_eh"
    if kind == "['lm_head']['W']":
        return "gradient_head"
    if kind.endswith(ROUTED_KINDS):
        return "gradient_routed"
    if kind.endswith(ROUTER_KINDS):
        return "gradient_router"
    return "gradient_dense"


def passed_limits(row: dict, loss_tolerance: float) -> list:
    """The limits that one row of readings is over."""
    over = [name for name in ("logits_rms_over_spread",
                              "mtp_logits_rms_over_spread")
            if row[name] > LIMITS[name]]
    if row["loss_rel_err"] > loss_tolerance:
        over.append("loss")
    for kind, err in row.get("gradients", {}).items():
        name = gradient_limit(kind)
        if err > LIMITS[name] and name not in over:
            over.append(name)
    return over


def verdict(result: dict, loss_tolerance: float) -> list:
    """What is wrong with a seed's readings: the system over a limit, the
    float8 control inside one it was read against (each limit the row
    carries a reading for), or the float32 system's gradients away from
    the reference's. Empty when sound."""
    rows = result["rows"]
    faults = [f"system over {name}"
              for name in passed_limits(rows["system"], loss_tolerance)]
    for name in MUST_BE_REFUSED:
        row = rows[name]
        over = passed_limits(row, loss_tolerance)
        read = ["logits_rms_over_spread", "mtp_logits_rms_over_spread",
                "loss"] + sorted({gradient_limit(kind)
                                  for kind in row.get("gradients", {})})
        faults += [f"{name} is inside {limit}" for limit in read
                   if limit not in over]
    f32 = rows.get("system_float32", {}).get("gradients", {})
    faults += [f"system_float32 gradient of {kind} off by {err:.3g}"
               for kind, err in f32.items() if err > FLOAT32_GRADIENT_LIMIT]
    return faults


def _keep(result):
    keep = ROOT / "chiprun_out"
    keep.mkdir(exist_ok=True)
    with open(keep / "glm4_moe_lite_chip_check.jsonl", "a") as fh:
        fh.write(json.dumps(result) + "\n")
    print(json.dumps(result), flush=True)


def _kind(path) -> str:
    import jax
    group = path[0].key
    return jax.tree_util.keystr(path if group in WHOLE_PATH else path[1:])


def check(seed: int, gradients: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.models.base import cast_params
    from yardstick.weights import init_on_device

    cell = cells.resolve_cell(CELL, ROOT)
    cfg = cell.config
    build, reference = cells.load_build(cell), cells.load_reference(cell)

    def system(config):
        model = init_on_device(build.build(config, seed), seed)
        # no optimizer state here: what the float32 backward needs
        model.train_state = model.train_state._replace(opt_state=None)
        return model

    model = system(cfg)
    ts = model.train_state
    params, state = ts.params, ts.model_state
    batch = build.rows_with_labels(cfg, seed, 2)
    assert np.array_equal(build.check_batch(cfg, seed, 2).labels,
                          batch.labels)   # the harness's rows
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)

    @jax.jit
    def apart(a, b):
        d = a - b
        return {"rms_over_spread": jnp.sqrt(jnp.mean(d * d)) / jnp.std(b),
                "max_diff": jnp.max(jnp.abs(d)),
                "argmax_agree": jnp.mean(jnp.argmax(a, -1)
                                         == jnp.argmax(b, -1))}

    head = model._nodes["lm_head"].layer

    @jax.jit
    def system_heads(params, state, ids):
        """Both heads' logits as the system computes them: the module's
        output through the head's own ``_logits``."""
        acts, _ = model._walk(params, state, {"ids": ids},
                              {"__default__": None}, False, None,
                              stop_before_loss=False)
        w = cast_params(params["lm_head"], cfg["compute_dtype"])
        return acts["lm_head"], head._logits(w, acts["mtp"])

    @jax.jit
    def system_terms(params, state, ids, labels):
        return model._loss(params, state, (ids,), (labels,), None, None,
                           None, ts.iteration)[1]["lm_head"]["lm_loss_terms"]

    want, want_mtp = reference.heads(cfg, params, state, (ids,))
    ref_main, ref_mtp, _ = (float(v) for v in reference.loss_terms(
        cfg, params, state, (ids,), (labels,)))
    ref_loss = float(reference.loss(cfg, params, state, (ids,), (labels,)))
    rows = {}

    def row(name, heads, terms, loss):
        r = {}
        for prefix, got, ref in (("", heads[0], want),
                                 ("mtp_", heads[1], want_mtp)):
            r.update({f"{prefix}logits_{k}": float(v)
                      for k, v in apart(got, ref).items()})
        main, mtp = (float(v) for v in terms)
        r.update(loss=float(loss),
                 loss_rel_err=abs(float(loss) - ref_loss) / ref_loss,
                 next_token_loss=main,
                 next_token_loss_rel_err=abs(main - ref_main) / ref_main,
                 mtp_loss=mtp, mtp_loss_rel_err=abs(mtp - ref_mtp) / ref_mtp)
        rows[name] = r

    row("system", system_heads(params, state, ids),
        system_terms(params, state, ids, labels), model.score(batch))
    for name, keys in CONTROLS.items():
        low = {**cfg, **keys}
        row(name, reference.heads(low, params, state, (ids,)),
            reference.loss_terms(low, params, state, (ids,), (labels,))[:2],
            reference.loss(low, params, state, (ids,), (labels,)))
    out = {"seed": seed, "device": jax.devices()[0].device_kind,
           "reference_loss": ref_loss,
           "reference_next_token_loss": ref_main,
           "reference_mtp_loss": ref_mtp,
           "reference_logits_spread": float(jnp.std(want)),
           "reference_mtp_logits_spread": float(jnp.std(want_mtp)),
           "rows": rows}
    del want, want_mtp
    if not gradients:
        return out

    one, one_labels = ids[:1], labels[:1]

    def system_loss(net):
        def fn(p, i, l):
            return net._loss(p, state, (i,), (l,), None, None, None,
                             ts.iteration)[0]
        return fn

    model32 = system({**cfg, "compute_dtype": "float32"})
    model32.train_state = ts            # the same weights, held once

    def highest(fn):
        def at_highest(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return at_highest

    losses = {"system": system_loss(model),
              "system_float32": highest(system_loss(model32)),
              **{name: reference.loss_fn({**cfg, **keys}, state)
                 for name, keys in CONTROLS.items()}}

    def grad_of(fn, groups):
        """The gradient with respect to some top-level groups of the tree;
        the whole tree goes in as an argument (a closure would bake the
        weights into the program as constants)."""
        def wrt(sub, whole, i, l):
            return fn({**whole, **sub}, i, l)
        return jax.jit(jax.grad(wrt))({g: params[g] for g in groups},
                                      params, one, one_labels)

    @jax.jit
    def rel_err(g, g_ref):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.linalg.norm(a - b) / jnp.linalg.norm(b),
            g, g_ref)

    names = sorted(params)
    for groups in (names[:len(names) // 2], names[len(names) // 2:]):
        g_ref = grad_of(reference.loss_fn(cfg, state), groups)
        for name, fn in losses.items():
            with (_nemotron._flash_tiles(512) if name == "system_float32"
                  else contextlib.nullcontext()):
                errs = rel_err(grad_of(fn, groups), g_ref)
            worst = rows.setdefault(name, {}).setdefault("gradients", {})
            for path, e in jax.tree_util.tree_leaves_with_path(errs):
                kind = _kind(path)
                worst[kind] = max(worst.get(kind, 0.0), float(np.asarray(e)))
        del g_ref
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-gradients", action="store_true",
                    help="logits and losses only, on every seed")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the comparison at published widths needs a TPU; "
                         f"found {jax.devices()[0].platform!r}. Not run.")
    import deeplearning4j_tpu  # noqa: F401  (applies the compile-cache rule)
    tol = float(cells.resolve_cell(CELL, ROOT).config["loss_tolerance"])
    faults = []
    for n, seed in enumerate(args.seeds):
        result = check(seed, gradients=n == 0 and not args.no_gradients)
        result["faults"] = verdict(result, tol)
        _keep(result)
        faults += result["faults"]
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
