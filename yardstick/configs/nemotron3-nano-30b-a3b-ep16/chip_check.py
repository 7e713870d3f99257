"""The builder's comparison on the chip, at the published widths and the
timed sizes: ``python yardstick/configs/nemotron3-nano-30b-a3b-ep16/
chip_check.py --seeds 21 22 23`` (a TPU; some minutes a seed). Exit code 1
when a limit below is passed, or when a control that has to be refused is
let through.

For each seed: weights from the seed as the cell makes them, rows of 8,192
token ids from ``rows_with_labels``; then four numbers for the system and
for each control, every one against the float32 reference (token-by-token
in its Mamba-2 layers):

- ``logits``: root mean square of the difference over 2 rows, as a share
  of the reference logits' own spread;
- ``loss``: what the harness compares and the cell's ``loss_tolerance``
  limits: relative difference of the score on ``check_batch``, the same 2
  rows without a label, which is the routers' balance term alone. Its
  error is one-signed and grows with the square of the rounding step;
- ``training_loss``: relative difference of the whole training loss on
  the labelled rows, next-token cross-entropy plus balance term. No limit:
  the next-token term's error is a signed mean of rounding errors, in the
  float8 control a draw about zero (PERF.md section 6);
- ``gradients`` (the first seed only: most of the script's minutes): on 1
  labelled row, ``|g - g_ref| / |g_ref|`` parameter by parameter, the
  worst by kind of parameter (dense, routed experts, router); half of the
  tree's top-level groups at a time so that the float32 reference's
  backward pass fits beside the weights.

The system is ``model.output`` / ``model.score`` / the gradient of
``model._loss`` at the configuration's bfloat16 compute. The controls are
the reference itself in a lower precision (its ``control_operand_dtype``):
with every matrix product's operands rounded to float8 (e4m3) — the
nearest precision below the configuration's, which the limits have to
refuse — and rounded to bfloat16 (what rounding alone does to a sound
program). One more row is the system at float32 compute and ``highest``
matrix products: where its gradients meet the reference's, the bfloat16
system's distance is rounding and not the backward pass (the chunked
Mamba-2 scan against the recurrence, the held experts' backward written
by hand).

Prints one JSON object per seed and appends it to
``chiprun_out/nemotron_h_chip_check.jsonl``. Nothing here is timed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

CELL = "nemotron3-nano-30b-a3b-ep16.fit-seq8k"

# Each limit lies between what the bfloat16 system read on the chip and
# what the float8 control read (PERF.md section 6 has both, by seed).
# ``loss`` is the configuration's ``loss_tolerance``, read from its file:
# the one limit the harness has, so the float8 control has to be over it.
LIMITS = {
    # system 3.48-4.34%, reference at bfloat16 operands 2.95-3.67%, float8
    # control 17.3-19.1% (seeds 21-29 and 51-56)
    "logits_rms_over_spread": 0.085,
    # seed 21, the worst leaf by kind; system / bfloat16 operands / float8
    # (the loss with the routers' balance term at 0.1)
    "gradient_dense": 0.25,      # 5.9% (dt_bias) / 4.5% / 100-235%
    "gradient_routed": 0.35,     # 14.6% (w_up) / 13.3% / 100%
    "gradient_router": 0.13,     # 4.7% / 3.5% / 35%
}
ROUTED_KINDS = ("['mixer']['w_up']", "['mixer']['w_down']")
ROUTER_KINDS = ("['mixer']['router']",)
# at float32 compute the system's gradients are the reference's, but for
# the few positions whose sixth expert is a near tie and the order of
# summation in the flash kernels and the chunked scan (read: at most
# 0.36%, the routed experts' w_up; the Mamba-2 layers' 0.04-0.08%)
FLOAT32_GRADIENT_LIMIT = 0.03

CONTROLS = {
    "reference_operands_float8": {"control_operand_dtype": "float8_e4m3fn"},
    "reference_operands_bfloat16": {"control_operand_dtype": "bfloat16"},
}
MUST_BE_REFUSED = ("reference_operands_float8",)


@contextlib.contextmanager
def _flash_tiles(block):
    """Under ``highest`` the flash kernels' float32 products need more of
    the scoped VMEM than the default tiles leave, so the float32 row, and
    it alone, traces them at ``block`` x ``block`` (which divides the
    8,192 positions)."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    before = pk._DEF_BLOCK_Q, pk._DEF_BLOCK_K
    pk._DEF_BLOCK_Q = pk._DEF_BLOCK_K = block
    try:
        yield
    finally:
        pk._DEF_BLOCK_Q, pk._DEF_BLOCK_K = before


def _keep(result):
    keep = ROOT / "chiprun_out"
    keep.mkdir(exist_ok=True)
    with open(keep / "nemotron_h_chip_check.jsonl", "a") as fh:
        fh.write(json.dumps(result) + "\n")
    print(json.dumps(result), flush=True)


def passed_limits(row: dict, loss_tolerance: float) -> list:
    """The limits that one row of readings (``logits_rms_over_spread``,
    ``loss_rel_err`` and, where taken, ``gradients`` by kind) is over."""
    over = []
    if row["logits_rms_over_spread"] > LIMITS["logits_rms_over_spread"]:
        over.append("logits_rms_over_spread")
    if row["loss_rel_err"] > loss_tolerance:
        over.append("loss")
    for kind, err in row.get("gradients", {}).items():
        name = ("gradient_routed" if kind.endswith(ROUTED_KINDS)
                else "gradient_router" if kind.endswith(ROUTER_KINDS)
                else "gradient_dense")
        if err > LIMITS[name] and name not in over:
            over.append(name)
    return over


def verdict(result: dict, loss_tolerance: float) -> list:
    """What is wrong with a seed's readings: the system over a limit, a
    control that has to be refused inside the harness's own limit (the
    loss's: ``fit_loop`` has no other), or the float32 system's gradients
    away from the reference's. Empty when sound."""
    rows = result["rows"]
    faults = [f"system over {name}"
              for name in passed_limits(rows["system"], loss_tolerance)]
    for name in MUST_BE_REFUSED:
        if "loss" not in passed_limits(rows[name], loss_tolerance):
            faults.append(f"{name} is inside the harness's limit")
    f32 = rows.get("system_float32", {}).get("gradients", {})
    faults += [f"system_float32 gradient of {kind} off by {err:.3g}"
               for kind, err in f32.items() if err > FLOAT32_GRADIENT_LIMIT]
    return faults


def check(seed: int, gradients: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from yardstick import cells
    from yardstick.weights import init_on_device

    cell = cells.resolve_cell(CELL, ROOT)
    cfg = cell.config
    build, reference = cells.load_build(cell), cells.load_reference(cell)

    def system(config):
        model = init_on_device(build.build(config, seed), seed)
        # no optimizer state here: 5.3 GB that the float32 backward needs
        model.train_state = model.train_state._replace(opt_state=None)
        return model

    model = system(cfg)
    ts = model.train_state
    params, state = ts.params, ts.model_state
    batch = build.rows_with_labels(cfg, seed, 2)
    check = build.check_batch(cfg, seed, 2)     # the same rows, no label
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    none = jnp.asarray(check.labels)

    @jax.jit
    def apart(a, b):
        d = a - b
        return {"logits_rms_over_spread":
                jnp.sqrt(jnp.mean(d * d)) / jnp.std(b),
                "logits_max_diff": jnp.max(jnp.abs(d)),
                "argmax_agree": jnp.mean(jnp.argmax(a, -1)
                                         == jnp.argmax(b, -1))}

    want = reference.logits(cfg, params, state, (ids,))
    ref_training = float(reference.loss(cfg, params, state, (ids,),
                                        (labels,)))
    ref_loss = float(reference.loss(cfg, params, state, (ids,), (none,)))
    rows = {}

    def row(name, logits, training, loss):
        rows[name] = {k: float(v) for k, v in apart(logits, want).items()}
        rows[name].update(
            loss=float(loss),
            loss_rel_err=abs(float(loss) - ref_loss) / ref_loss,
            training_loss=float(training),
            training_loss_rel_err=abs(float(training) - ref_training)
            / ref_training)

    row("system", model.output(batch.features), model.score(batch),
        model.score(check))
    for name, keys in CONTROLS.items():
        low = {**cfg, **keys}
        row(name, reference.logits(low, params, state, (ids,)),
            reference.loss(low, params, state, (ids,), (labels,)),
            reference.loss(low, params, state, (ids,), (none,)))
    out = {"seed": seed, "device": jax.devices()[0].device_kind,
           "reference_loss": ref_loss,
           "reference_training_loss": ref_training,
           "reference_logits_spread": float(jnp.std(want)), "rows": rows}
    del want
    if not gradients:
        return out

    one, one_labels = ids[:1], labels[:1]

    def system_loss(net):
        def fn(p, i, l):
            return net._loss(p, state, i, l, None, None, None,
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
                 for name, keys in CONTROLS.items()
                 if "control_operand_dtype" in keys}}

    def grad_of(fn, groups):
        """The gradient with respect to some top-level groups of the tree;
        the whole tree goes in as an argument (a closure would bake 2.7 GB
        of weights into the program as constants)."""
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
            with (_flash_tiles(512) if name == "system_float32"
                  else contextlib.nullcontext()):
                errs = rel_err(grad_of(fn, groups), g_ref)
            worst = rows.setdefault(name, {}).setdefault("gradients", {})
            for path, e in jax.tree_util.tree_leaves_with_path(errs):
                kind = jax.tree_util.keystr(path[1:])
                worst[kind] = max(worst.get(kind, 0.0), float(np.asarray(e)))
        del g_ref
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-gradients", action="store_true",
                    help="logits and loss only, on every seed: a minute a "
                         "seed, for the spread of the loss's reading")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the comparison at published widths needs a TPU; "
                         f"found {jax.devices()[0].platform!r}. Not run.")
    import deeplearning4j_tpu  # noqa: F401  (applies the compile-cache rule)
    from yardstick import cells
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
