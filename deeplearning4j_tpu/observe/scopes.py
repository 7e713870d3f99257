"""Which ``jax.named_scope`` each operation of a compiled step belongs to.

A device trace names an operation by its HLO instruction (``fusion.163``)
and says nothing of the layer that asked for it. The compiled program
does: every instruction's ``metadata`` carries the ``op_name`` path that
JAX built while tracing, named scopes included
(``jit(step)/.../transpose(jvp(gdn.scan))/while/body/dot_general``). When
a span tracer is enabled and the model's layers declare scopes
(``named_scopes``), the fit loop lowers and compiles the step it is about
to run once more (the persistent cache answers where it is on), reads that
text here and hands instruction -> op_name to the tracer as the ``table``
of a zero-length ``step_scopes`` span (cat ``step``), once per ``fit()``
call, for whoever reduces the device trace: the yardstick's per-scope
readers. The same text says how many Pallas kernels the step launches
under each declared scope (a kernel is a custom call to Mosaic's target),
published with the span as the gauge ``dl4j_step_kernel_calls{scope}``.
Nothing is lowered, compiled or recorded when tracing is off.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
# a Pallas kernel's launch in a program compiled for a TPU: a custom call
# to Mosaic whose ``op_name`` ends in the primitive's name. The values it
# returns (``get-tuple-element``), its constants and copies carry the same
# ``op_name`` and are no launches; XLA's own Mosaic kernels
# (``lax.ragged_dot``) are no ``pallas_call``
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_KERNEL_OP = "pallas_call"

STEP_KERNEL_CALLS_GAUGE = (
    "dl4j_step_kernel_calls",
    "Pallas kernel launches of the compiled train step under a declared "
    "named scope, forward, recomputation and backward together (label: the "
    "scope; 0: the scope's layers took no kernel)")


def scopes_in_hlo(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` for every instruction of an HLO module's
    text that carries an ``op_name``."""
    found = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found[m.group(1)] = m.group(2)
    return found


def kernels_in_hlo(text: str) -> Dict[str, str]:
    """``scopes_in_hlo`` of the Pallas kernels' launches alone."""
    launches = scopes_in_hlo("\n".join(
        line for line in text.splitlines() if _KERNEL_TARGET in line))
    return {name: op_name for name, op_name in launches.items()
            if op_name.endswith(_KERNEL_OP)}


def kernel_calls(kernels: Dict[str, str],
                 scopes: Iterable[str]) -> Dict[str, int]:
    """``{scope: launches}`` for each of ``scopes``: the entries of
    ``kernels`` (instruction -> op_name, ``kernels_in_hlo``'s) whose path
    holds the scope as a component, bare or wrapped by a transformation
    (``transpose(jvp(attn.gated))``)."""
    return {scope: sum(
        bool(re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", op_name))
        for op_name in kernels.values()) for scope in scopes}


def publish_kernel_calls(calls: Dict[str, int]) -> None:
    from deeplearning4j_tpu.observe.registry import default_registry
    gauge = default_registry().gauge(*STEP_KERNEL_CALLS_GAUGE)
    for scope, n in calls.items():
        gauge.set(n, scope=scope)
