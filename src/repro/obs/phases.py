"""The train step's device phases, and which compiled instruction is in each.

The step builders (:func:`repro.models.recsys.make_sparse_train_step`,
:func:`~repro.models.recsys.make_mesh_train_step`) and the fused boundary
of :meth:`repro.fe.modelfeed.ModelFeed.make_step` wrap each part of the
step in ``jax.named_scope(<phase>)``. The scope changes only the HLO
metadata: the compiled program keeps every op, fusion and number, and each
top-level instruction's ``op_name`` names its phase
(``jit(_boundary)/dedup/...``). :func:`phase_map` reads them back from the
compiled program's text, so that a device profile's per-op times sum by
phase.

* ``feed`` — ``ModelFeed.apply`` inside the fused jit;
* ``dedup`` — gathering the batch's ids, their concat and dedup (the mesh
  step's pool all-gather included);
* ``lookup`` — the working-set gather from the table (one-chip step);
* ``exchange`` — the owned-row contribution and its reduction (mesh);
* ``fwd_bwd`` — forward and backward of the loss;
* ``grad_reduce`` — working-set and dense gradient reductions (mesh);
* ``dense_update`` — the dense optimizer;
* ``embed_update`` — Adagrad on the working rows, and the row and
  accumulator scatters.

Like the rest of :mod:`repro.obs`, this module imports neither jax nor
numpy.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

PHASES: Tuple[str, ...] = (
    "feed", "dedup", "lookup", "exchange", "fwd_bwd", "grad_reduce",
    "dense_update", "embed_update")
(FEED, DEDUP, LOOKUP, EXCHANGE, FWD_BWD, GRAD_REDUCE, DENSE_UPDATE,
 EMBED_UPDATE) = PHASES

# The tracer annotation under which ModelFeed's step records its map.
STEP_PHASES = "step.phases"

# Opcodes that name values but run nothing on the device.
NO_WORK = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id"})

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def entry_instructions(hlo_text: str
                       ) -> Dict[str, Tuple[str, Optional[str], List[str]]]:
    """``{name: (opcode, op_name or None, operand names)}`` for each
    instruction of the ENTRY computation of an HLO module's text."""
    out: Dict[str, Tuple[str, Optional[str], List[str]]] = {}
    inside = False
    for line in hlo_text.splitlines():
        if not inside:
            inside = line.startswith("ENTRY ")
            continue
        if line.startswith("}"):
            break
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        rest = " " + rest
        op = _OPCODE.search(rest)
        opcode = op.group(1) if op else ""
        body = rest[op.end():] if op else rest
        meta = _OP_NAME.search(body)
        out[name] = (opcode, meta.group(1) if meta else None,
                     _OPERAND.findall(body.split("metadata={")[0]))
    for name, (opcode, meta, operands) in out.items():
        out[name] = (opcode, meta, [o for o in operands if o in out])
    return out


def phase_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost phase scope an ``op_name`` names, or None."""
    for part in reversed((op_name or "").split("/")):
        if part in PHASES:
            return part
    return None


def phase_map(hlo_text: str) -> Dict[str, str]:
    """``{top-level instruction name: phase}`` of a compiled module.

    An instruction takes the phase its ``op_name`` names. One the
    compiler left without it (a copy, a ``reduce-window``, a broadcast)
    takes the phase of the first instruction that consumes its output and
    has one, or else that of the first it reads, or else (a copy of a
    constant into the outputs) that of the instruction scheduled before it.
    """
    instrs = entry_instructions(hlo_text)
    users: Dict[str, List[str]] = {n: [] for n in instrs}
    for name, (_, _, operands) in instrs.items():
        for o in operands:
            users[o].append(name)
    own = {n: phase_of(meta) for n, (_, meta, _) in instrs.items()}

    def search(start: str, edges: Dict[str, List[str]]) -> Optional[str]:
        seen, stack = {start}, list(reversed(edges[start]))
        while stack:
            n = stack.pop()
            if own[n] is not None:
                return own[n]
            if n not in seen:
                seen.add(n)
                stack.extend(reversed(edges[n]))
        return None

    operands = {n: ops for n, (_, _, ops) in instrs.items()}
    out: Dict[str, str] = {}
    last: Optional[str] = None
    for name in instrs:
        phase = (own[name] or search(name, users) or search(name, operands)
                 or last)
        if phase is not None:
            out[name] = last = phase
    return out


def unmapped_work(hlo_text: str) -> List[str]:
    """Top-level instructions that do work and reach no phase."""
    mapped = phase_map(hlo_text)
    return [n for n, (opcode, _, _) in entry_instructions(hlo_text).items()
            if opcode not in NO_WORK and n not in mapped]
