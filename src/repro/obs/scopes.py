"""Named scopes of a compiled program, by HLO instruction.

The aggregation dispatch (``core.adaptgear``) puts each subgraph's kernel
under ``jax.named_scope("agg/<tier>/<kernel>")``.  The scope lands in
every op's ``metadata={op_name=...}`` of the optimized HLO; transforms
wrap it (``transpose(jvp(agg/inter/csr))/...`` for the backward pass).
:func:`op_scopes` reads those names back from a compiled executable, so a
profile's ``XLA Ops`` event (``%fusion.114 = ...``) can be put down to
the tier and kernel that produced it.
"""
from __future__ import annotations

import re

__all__ = ["op_scopes"]

# one instruction per line: `[ROOT] %name = <shape> <opcode>(...), ...,
# metadata={op_name="..." ...}`
_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bmetadata=\{[^}]*?'
    r'\bop_name="((?:[^"\\]|\\.)*)"', re.M)


def op_scopes(compiled) -> dict[str, str]:
    """``{HLO instruction name: op_name}`` for every instruction of a
    compiled executable (``jax.stages.Compiled``, or anything with
    ``as_text()``) whose metadata names its source op."""
    return {m.group(1): m.group(2)
            for m in _INSTR.finditer(compiled.as_text())}

