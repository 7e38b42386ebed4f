"""Static cost model of a compiled XLA executable: flops, bytes, and the
collective inventory.

The perf plane's ground truth is the compiled artifact itself, not a
wall clock: `Compiled.cost_analysis()` carries XLA's own flop/byte
accounting, `memory_analysis()` the buffer budget, and the compiled HLO
text names every collective the partitioner inserted — operand shapes,
element types, and replica groups included. This module turns those
three sources into plain dicts the journal, the scaling bench, and the
regression gate can carry, with one cross-check that keeps the parser
honest: for a data-parallel training step, the summed all-reduce bytes
must equal the gradient-tree size (each device contributes its full
grad pytree to the reduction), so `predicted_allreduce_bytes` vs
`tree_bytes(grads)` is an end-to-end assertion on the whole chain —
sharding table -> partitioner -> HLO -> this parser.

Dependency-light on purpose: the HLO parser is pure regex over
`Compiled.as_text()` (no XLA proto imports), so it also digests HLO
dumped by other tools, and every extractor degrades to None/[] instead
of raising — a perf probe must never take down a warmup.
"""
from __future__ import annotations

import re
from typing import List, Optional

__all__ = [
    "COLLECTIVE_KINDS",
    "collective_inventory",
    "cost_summary",
    "hlo_text",
    "predicted_collective_bytes",
    "tree_bytes",
]

#: the collective op kinds the inventory recognizes (HLO opcode names);
#: check_journal's perf_collective enum is this tuple — keep in sync
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

#: HLO primitive element type -> bytes per element
DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e4m3b11fnuz": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

# one typed array shape inside an HLO line: f32[64,128] / bf16[] / pred[8]
_SHAPE_RE = re.compile(r"\b([a-z]\w*)\[([0-9,]*)\]")

# an HLO instruction line defining a collective:
#   %name = <shape-or-tuple> all-reduce(...), channel_id=1, replica_groups=...
# async pairs lower to `-start`/`-done`; only the start carries the
# payload shape, so `-done` lines are skipped to avoid double counting.
# A tuple shape may nest one level of parentheses: TPU layouts carry
# tiling, `(f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)})`.
_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<shape>\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
    r"(?P<kind>" + "|".join(re.escape(k) for k in COLLECTIVE_KINDS) + r")"
    r"(?P<suffix>-start|-done)?\(")

_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|\[[^\]]*\]<=\[[^\]]*\])")


def _shape_bytes(shape_text: str):
    """(total_bytes, dtype, elements) summed over every typed array in
    `shape_text` (a tuple shape contributes all members)."""
    total = 0
    elements = 0
    dtype = None
    for m in _SHAPE_RE.finditer(shape_text):
        ty, dims = m.group(1), m.group(2)
        width = DTYPE_BYTES.get(ty)
        if width is None:
            continue  # token shapes (u32[] control deps) still match; sized 0-d below
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * width
        elements += n
        dtype = dtype or ty
    return total, dtype, elements


def _group_size(raw: Optional[str]) -> Optional[int]:
    """Participants per replica group, from either HLO form:
    iota `[1,8]<=[8]` (shape is [num_groups, group_size]) or the
    explicit `{{0,1},{2,3}}` list."""
    if not raw:
        return None
    if raw.startswith("[") and "<=" in raw:
        dims = raw[1:raw.index("]")].split(",")
        try:
            return int(dims[-1])
        except (ValueError, IndexError):
            return None
    if raw.startswith("{{"):
        first = raw[2:raw.index("}", 2)]
        return len([t for t in first.split(",") if t.strip() != ""])
    return None


def collective_inventory(hlo: str) -> List[dict]:
    """Every collective instruction in compiled HLO text, one dict each:

        {"kind", "dtype", "bytes", "elements", "group_size",
         "replica_groups", "channel_id", "op_name"}

    `bytes` is the per-device payload (sum over tuple operands).
    Unparseable lines are skipped, never fatal.
    """
    out: List[dict] = []
    for line in hlo.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None or m.group("suffix") == "-done":
            continue
        nbytes, dtype, elements = _shape_bytes(m.group("shape"))
        if nbytes <= 0:
            continue
        rg = _REPLICA_GROUPS_RE.search(line)
        ch = re.search(r"channel_id=(\d+)", line)
        op = re.search(r'op_name="([^"]*)"', line)
        out.append({
            "kind": m.group("kind"),
            "dtype": dtype,
            "bytes": int(nbytes),
            "elements": int(elements),
            "group_size": _group_size(rg.group(1) if rg else None),
            "replica_groups": rg.group(1) if rg else None,
            "channel_id": int(ch.group(1)) if ch else None,
            "op_name": op.group(1) if op else None,
        })
    return out


def predicted_collective_bytes(inventory: List[dict],
                               kind: Optional[str] = None) -> int:
    """Summed per-device payload bytes over the inventory (one `kind`,
    or every collective when kind is None)."""
    return sum(c["bytes"] for c in inventory
               if kind is None or c["kind"] == kind)


def hlo_text(compiled) -> Optional[str]:
    """Compiled HLO text of an executable, or None when the backend
    doesn't expose it (never raises)."""
    try:
        txt = compiled.as_text()
        return txt if isinstance(txt, str) and txt else None
    except Exception:
        return None


def cost_summary(compiled) -> dict:
    """XLA's own accounting for one compiled executable:

        {"flops", "bytes_accessed", "argument_bytes", "output_bytes",
         "temp_bytes", "generated_code_bytes"}

    cost_analysis() keys are per-device under SPMD. Missing analyses
    leave fields as None — a probe, not a requirement.
    """
    out = {"flops": None, "bytes_accessed": None, "argument_bytes": None,
           "output_bytes": None, "temp_bytes": None,
           "generated_code_bytes": None}
    try:
        ca = compiled.cost_analysis()
        if ca.get("flops", -1) >= 0:
            out["flops"] = float(ca["flops"])
        ba = ca.get("bytes accessed")
        if ba is not None and ba >= 0:
            out["bytes_accessed"] = float(ba)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
        out["output_bytes"] = int(ma.output_size_in_bytes)
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
        out["generated_code_bytes"] = int(ma.generated_code_size_in_bytes)
    except Exception:
        pass
    return out


def tree_bytes(tree) -> int:
    """Total bytes of every array leaf in a pytree (the gradient-tree
    size the all-reduce inventory is checked against)."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * np.dtype(dtype).itemsize
    return int(total)
