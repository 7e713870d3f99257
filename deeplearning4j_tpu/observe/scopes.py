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
readers. Nothing is lowered, compiled or recorded when tracing is off.
"""

from __future__ import annotations

import re
from typing import Dict

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')


def scopes_in_hlo(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` for every instruction of an HLO module's
    text that carries an ``op_name``."""
    found = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found[m.group(1)] = m.group(2)
    return found


def compiled_scopes(jitted_step, *args) -> Dict[str, str]:
    """Compile ``jitted_step`` for ``args`` again and read its
    instruction -> op_name map."""
    return scopes_in_hlo(jitted_step.lower(*args).compile().as_text())
