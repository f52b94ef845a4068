"""What XLA built for a step, read from the compiled module's text: whether
the KV pools (and a state cache's buffers) are updated where they are
(`count_pool_copies`), which weight-shaped results a step makes anew
(`count_weight_bytes_copied`) and whether an indexed layer's choice of rows
sorts a lane's scores (`count_select_sorts`).
`InferenceEngine.compiled_steps()` and tests/test_tpu_aot.py hold every
family's programs to them."""

from __future__ import annotations

import collections
import math
import re
from typing import Dict, Sequence

import jax

# `  ROOT %name = bf16[48,256,16,1664]{3,2,1,0:T(8,128)(2,1)} opcode(%a, %b),
#    attributes`; the type is a tuple of such for a multi-output fusion.
_HLO_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_HLO_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_HLO_LOOP = re.compile(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)")
# Opcodes that make a copy of their operand in another dtype, place or
# extent (`copy-start` is counted at its `copy-done`); `remat` is ours.
_MOVES = ("convert", "copy", "copy-done", "transpose", "slice",
          "dynamic-slice", "remat")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4}


def _parse_hlo(hlo_text: str):
    """(computations, roots, fused, trips) of a compiled module's text:
    computation -> {instruction: (result arrays [(dtype, [dims])], opcode,
    operand names, attributes)}; computation -> its root instruction; the
    computations `fusion`s call; loop body -> its trip count, where the
    loop's condition is `counter < constant` (a scan's)."""
    comps: Dict[str, Dict[str, tuple]] = {}
    roots: Dict[str, str] = {}
    trips: Dict[str, int] = {}
    bounds: Dict[str, int] = {}     # computation -> its last int constant
    current = None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            current = head.group(1)
            comps[current] = {}
            continue
        m = _HLO_INSTR.match(line)
        if m and current:
            root, name, result, opcode, rest = m.groups()
            operands, _, attrs = rest.partition(")")
            arrays = [(dtype, [int(d) for d in dims.split(",") if d])
                      for dtype, dims in _HLO_ARRAY.findall(result)]
            comps[current][name] = (
                arrays, opcode, re.findall(r"%([\w.\-]+)", operands), attrs)
            if root:
                roots[current] = name
            if opcode == "constant" and operands.isdigit():
                bounds[current] = int(operands)
            loop = opcode == "while" and _HLO_LOOP.search(attrs)
            if loop and "direction=LT" in comps[loop.group(1)][
                    roots[loop.group(1)]][3]:
                trips[loop.group(2)] = bounds.get(loop.group(1), 1)
    fused = {_called(instr[3]) for body in comps.values()
             for instr in body.values() if instr[1] == "fusion"}
    return comps, roots, fused, trips


def _called(attrs: str) -> str:
    return re.search(r"calls=%?([\w.\-]+)", attrs).group(1)


def _made_by(comps, roots, comp: str, name: str) -> str:
    """The opcode that makes an instruction's result: its own, or for a
    `fusion` (and through `bitcast`s) that of the called root."""
    _, opcode, operands, attrs = comps[comp].get(name, ([], "", [], ""))
    if opcode == "fusion":
        return _made_by(comps, roots, _called(attrs), roots[_called(attrs)])
    if opcode == "bitcast" and operands:
        return _made_by(comps, roots, comp, operands[0]) or opcode
    if opcode == "tuple":               # a multi-output fusion's root
        made = [_made_by(comps, roots, comp, o) for o in operands]
        return next((m for m in made if m in _MOVES), opcode)
    return opcode


def count_pool_copies(hlo_text: str, pool_shape: Sequence[int]) -> int:
    """Instructions of a compiled step that move the KV pool instead of
    touching rows and blocks of it: the result is the whole stored pool
    or whole layers of it (as a layer scan slices them out and stacks
    them back), and the instruction, or the root of the fusion it calls,
    is a `copy`, a `scatter`, a `dynamic-slice`, or a
    `dynamic-update-slice` whose update is itself whole layers.  A row or
    a block written into the pool is in place and not counted: where XLA
    cannot update in place it inserts a `copy`, which is.  Zero means the
    pool stays where it is."""
    n_layers, *block = (int(d) for d in pool_shape)
    comps, roots, fused, _ = _parse_hlo(hlo_text)

    def whole_layers(arrays) -> bool:
        return any(shape[-len(block):] == block
                   and n_layers % math.prod(shape[:-len(block)]) == 0
                   for _, shape in arrays)

    def moves(comp: str, name: str) -> bool:
        arrays, opcode, operands, attrs = comps[comp].get(
            name, ([], "", [], ""))
        if not whole_layers(arrays):
            return False
        if opcode == "fusion":
            return moves(_called(attrs), roots[_called(attrs)])
        if opcode == "tuple":           # a multi-output fusion's root
            return any(moves(comp, o) for o in operands)
        if opcode == "bitcast":         # a fusion's root behind a bitcast
            return moves(comp, operands[0])
        if opcode == "dynamic-update-slice":
            return whole_layers(comps[comp].get(operands[1], ([],))[0])
        return opcode in ("copy", "scatter", "dynamic-slice")

    return sum(moves(comp, name) for comp, body in comps.items()
               if comp not in fused for name in body)


def count_select_sorts(hlo_text: str, context: int) -> int:
    """Sorts of a [rows, context] array in a compiled step, `context` the
    positions a lane's table holds: what an indexed layer's choice of its
    `topk` rows was until PR 48, a stable sort of every lane's 17k scores
    for the best 2,048 (a seventh of dots3's step), and must not become
    again: `ops.attention.sparse_select` finds the k-th score by bisection
    and the places by counts.  A router's top-k over a row of experts is
    narrower and the expert dispatch's sort of its assignments is of rank
    1: neither is counted."""
    comps = _parse_hlo(hlo_text)[0]
    return sum(opcode == "sort" and any(
        len(dims) == 2 and dims[1] >= context for _, dims in arrays)
               for body in comps.values()
               for arrays, opcode, _, _ in body.values())


def count_weight_bytes_copied(hlo_text: str, weights) -> Dict[str, int]:
    """Bytes of weight-shaped results a compiled step makes in one run,
    by the opcode that makes them ({} when the weights are read where they
    are).  Weight-shaped: the shape of a matrix leaf of `weights` (the
    tree the step takes, arrays or their shapes), of a group of its
    leading dim (as a layer scan slices its groups out: leading dims that
    divide it, the rest equal) or of either with the last two dims
    swapped; counted where the instruction, or the root of the fusion it
    calls, copies its operand (`_MOVES`), times the trip counts of the
    scans around it.  A per-step `convert` is a leaf held in the wrong
    dtype, a `copy` or `transpose` one held the wrong way round, `remat` (an
    instruction XLA named `.remat`) a temporary made again; what a layer
    scan slices out of its stacked arguments reads `dynamic-slice` (and
    `copy-done` where XLA prefetches it)."""
    comps, roots, fused, trips = _parse_hlo(hlo_text)
    # (what the leading dims must divide, the dims that must follow them)
    forms = set()
    for x in jax.tree.leaves(weights):
        shape = tuple(x.shape)
        if len(shape) < 2:
            continue
        for s in (shape, (*shape[:-2], shape[-1], shape[-2])):
            forms.add((1, s))                       # the leaf
            if len(s) >= 3:
                forms.add((s[0], s[1:]))            # a group of its layers

    def weight_shaped(shape) -> bool:
        def fits(lead, rest):
            k = len(shape) - len(rest)
            return (k >= 0 and tuple(shape[k:]) == rest
                    and lead % math.prod(shape[:k]) == 0)
        return any(fits(lead, rest) for lead, rest in forms)

    # Runs of each computation per step: a loop body's trip count times
    # its caller's (computations reached by `call`s and fusions inherit).
    runs: Dict[str, int] = {}

    def visit(comp: str, n: int) -> None:
        runs[comp] = n
        for _, opcode, _, attrs in comps.get(comp, {}).values():
            for callee in re.findall(
                    r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)", attrs):
                if callee not in runs and callee not in fused:
                    visit(callee, n * trips.get(callee, 1))

    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo_text, re.M)
    if entry:
        visit(entry.group(1), 1)
    out: Dict[str, int] = collections.Counter()
    for comp, body in comps.items():
        if comp in fused:
            continue
        for name, (arrays, opcode, _, _) in body.items():
            made = ("remat" if ".remat" in name
                    else _made_by(comps, roots, comp, name)
                    if opcode == "fusion" else opcode)
            if made not in _MOVES:
                continue
            size = sum(_DTYPE_BYTES.get(dtype, 4) * math.prod(shape)
                       for dtype, shape in arrays if weight_shaped(shape))
            if size:
                out[made] += size * runs.get(comp, 1)
    return dict(out)
