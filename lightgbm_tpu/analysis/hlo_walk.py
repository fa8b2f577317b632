"""Shared compiled-HLO text walker.

One parser for every pass that reads compiled programs: the
collective-traffic auditor (``parallel/comms.py``), the HLO lint rules
(``analysis/hlo_lint.py``), and ad-hoc audits in tests. XLA's
``Compiled.as_text()`` HLO is line-oriented — one op per line of the
form::

    %name = f32[8,64]{1,0} opcode(operands...), attr=..., \
        metadata={op_name="jit(f)/phase/op" ...}

so a regex walk recovers every op's opcode, result shapes (with byte
sizes), and the ``op_name`` metadata that carries ``jax.named_scope``
prefixes (the profiler phases of ``phases.py``). This module owns the
regexes and the dtype byte table; the consumers own their accounting.
"""

from __future__ import annotations

import dataclasses
import re
from typing import List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["HloOp", "DTYPE_BYTES", "COLLECTIVE_KINDS", "base_opcode",
           "parse_ops",
           "parse_all_ops", "parse_collective_ops",
           "input_output_aliases", "lower_hlo"]

COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "all-to-all", "collective-permute")

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
               "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
               "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

# `%name = f32[2,4]{1,0} <opcode>(...)` — tuple outputs wrap the shapes
# in parentheses. `-start` covers the async TPU forms; `-done` ops carry
# no payload of their own and are skipped by the collective walk.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CCT_RE = re.compile(r'custom_call_target="([^"]*)"')
# generic op line: `[ROOT] %instr.N = <out-spec> opcode(...)`; the `%`
# sigil is optional (newer HLO dumps drop it). The out spec is one shape
# or a parenthesized tuple of shapes, see _split_instruction
_HEAD_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[A-Za-z_][\w.-]*)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*(?P<op>[\w-]+)\(")


@dataclasses.dataclass(frozen=True)
class HloOp:
    """One parsed op line of a compiled program."""
    opcode: str                     # e.g. all-reduce | constant | ...
    shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]
    out_bytes: int                  # bytes of the op's RESULT (per chip)
    op_name: str                    # HLO metadata (named_scope prefixes)
    custom_call_target: str = ""    # for custom-call ops
    name: str = ""                  # LHS instruction name (%name = ...)


def base_opcode(opcode: str) -> str:
    """An async ``-start`` form's opcode without the suffix."""
    return opcode[:-6] if opcode.endswith("-start") else opcode


def shape_bytes(text: str):
    """Parse `dtype[dims]` result shapes out of an op's output spec;
    returns (shapes, total_bytes). Layout annotations like {1,0} are
    skipped via the dtype table."""
    shapes = []
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        shapes.append((dt, shape))
        nbytes += int(np.prod(shape, dtype=np.int64)) * DTYPE_BYTES[dt]
    return tuple(shapes), nbytes


def parse_ops(hlo_text: str, opcodes: Sequence[str],
              skip_done: bool = True) -> List[HloOp]:
    """Extract every op whose opcode is in ``opcodes`` from compiled-HLO
    text (async ``-start`` forms included, ``-done`` halves skipped).
    Lines are split by :func:`_split_instruction`, so the tiled layouts
    of a TPU's text (``{1,0:T(8,128)S(1)}``) are read as well as a
    CPU's."""
    want = set(opcodes)
    ops = []
    for line in hlo_text.splitlines():
        parts = _split_instruction(line)
        if parts is None:
            continue
        _name, out, opcode, _rest = parts
        if opcode.endswith("-done") and skip_done:
            continue
        kind = base_opcode(opcode)
        if kind not in want:
            continue
        shapes, nbytes = shape_bytes(out)
        nm = _NAME_RE.search(line)
        cct = _CCT_RE.search(line)
        ops.append(HloOp(opcode=kind, shapes=shapes, out_bytes=nbytes,
                         op_name=nm.group(1) if nm else "",
                         custom_call_target=cct.group(1) if cct else ""))
    return ops


def _split_instruction(line: str):
    """(name, out spec, opcode, rest after the opening parenthesis) of an
    instruction line, or None. The out spec of a tuple holds spaces,
    ``/*index=5*/`` comments and the parentheses of TPU tiled layouts
    (``{1,0:T(8,128)}``), so it is skipped by balancing parentheses
    instead of by a pattern."""
    m = _HEAD_RE.match(line)
    if m is None:
        return None
    i = m.end()
    if i < len(line) and line[i] == "(":
        depth, j = 0, i
        while j < len(line):
            depth += {"(": 1, ")": -1}.get(line[j], 0)
            j += 1
            if depth == 0:
                break
        out = line[i:j]
    else:
        j = line.find(" ", i)
        if j < 0:
            return None
        out = line[i:j]
    mo = _OPCODE_RE.match(line, j)
    if mo is None:
        return None
    return m.group("name"), out, mo.group("op"), line[mo.end():]


def parse_all_ops(hlo_text: str) -> List[HloOp]:
    """Every op line of the module (all computations, fusions
    included), with the LHS instruction ``name`` filled — the key the
    profiler's trace events carry as ``hlo_op``, so this is what the
    instruction→phase map (telemetry/costmodel.py) is built from."""
    ops = []
    for line in hlo_text.splitlines():
        parts = _split_instruction(line)
        if parts is None:
            continue
        name, out, opcode, _rest = parts
        shapes, nbytes = shape_bytes(out)
        nm = _NAME_RE.search(line)
        cct = _CCT_RE.search(line)
        ops.append(HloOp(opcode=opcode, shapes=shapes,
                         out_bytes=nbytes,
                         op_name=nm.group(1) if nm else "",
                         custom_call_target=cct.group(1) if cct else "",
                         name=name))
    return ops


def parse_collective_ops(hlo_text: str) -> List[HloOp]:
    """Every collective op (any of :data:`COLLECTIVE_KINDS`)."""
    return parse_ops(hlo_text, COLLECTIVE_KINDS)


def input_output_aliases(hlo_text: str) -> str:
    """The module header's ``input_output_alias`` body ('' when the
    program donates nothing). Non-empty means some input buffer is
    aliased to an output — a donated argument. The body nests braces
    (``{ {1}: (0, {}, may-alias) }``), so this brace-counts instead of
    regexing."""
    key = "input_output_alias={"
    i = hlo_text.find(key)
    if i < 0:
        return ""
    j = i + len(key)
    depth = 1
    while j < len(hlo_text) and depth:
        depth += {"{": 1, "}": -1}.get(hlo_text[j], 0)
        j += 1
    return hlo_text[i + len(key):j - 1].strip()


def lower_hlo(fn, *args, jit_kwargs: Optional[dict] = None,
              **kwargs) -> str:
    """Compiled (post-SPMD) HLO text of ``jit(fn)(*args, **kwargs)``.
    Nested jits (the plans' inner pjits) inline into the one lowered
    module, so the whole program's ops are visible. ``fn`` may already
    be jitted — jit of a jitted fn is the inner fn's cache."""
    import jax
    jf = jax.jit(fn, **(jit_kwargs or {}))
    return jf.lower(*args, **kwargs).compile().as_text()
