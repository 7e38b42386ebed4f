"""Per-op HBM traffic breakdown of the flagship train step (round 4).

The bench's aggregate number (77.9 GB/step at batch 256 ~= 92% of v5e HBM
bandwidth) says the step is memory-bound but not WHERE the bytes go. This
derives the breakdown statically from the compiled executable's post-optimization HLO:
every top-level instruction of the entry computation reads its operands from
HBM and writes its output to HBM (XLA materializes exactly these buffers;
everything else lives inside fusions), so

    bytes(instr) ~= sum(operand buffer sizes) + output buffer size

which is the same accounting XLA's own cost analysis uses for its aggregate
"bytes accessed". The report ranks instructions, groups them into classes
(conv fwd / conv dgrad+wgrad / BN-ish fusions / optimizer / copies ...), and
cross-checks the grand total against `cost_analysis()["bytes accessed"]`.

Writes artifacts/hbm_breakdown_r04.json. Run on the chip (layouts and
fusion decisions are backend-specific).
"""
from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Bytes of one (possibly tuple) HLO shape string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


# one instruction line: "  %name = <shape> opcode(...)" or "  name = ..."
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+((?:\([^=]*?\))|(?:[\w\[\],:{}()*#\s]+?))\s+"
    r"([\w\-]+)\("
)


def parse_entry(hlo_text: str):
    """Yield (name, shape_str, opcode, operand_names, line) for the entry
    computation's top-level instructions."""
    lines = hlo_text.splitlines()
    in_entry = False
    for ln in lines:
        if ln.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and ln.startswith("}"):
            break
        if not in_entry:
            continue
        m = _INSTR_RE.match(ln)
        if not m:
            continue
        name, shape_str, opcode = m.group(1), m.group(2), m.group(3)
        # operand names: %foo references after the opcode's open paren
        rest = ln[m.end():]
        # strip nested calls=/to_apply= references and attribute payloads
        args = rest.split("), ")[0]
        operands = re.findall(r"%([\w.\-]+)", args)
        yield name, shape_str, opcode, operands, ln


def classify(opcode: str, line: str) -> str:
    """Bucket an entry instruction for the report."""
    if opcode == "fusion":
        if "conv" in line and "kind=kOutput" in line:
            return "conv+epilogue fusion"
        if "reduce" in line or "kind=kInput" in line:
            return "reduce fusion (BN stats & grads)"
        return "elementwise fusion (BN apply/residual/opt)"
    if opcode == "convolution":
        return "convolution (unfused)"
    if opcode in ("copy", "copy-start", "copy-done", "transpose"):
        return "copy/layout"
    if opcode in ("all-reduce", "all-gather", "reduce-scatter"):
        return "collective"
    if opcode in ("custom-call",):
        return "custom-call"
    if opcode in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast-convert"):
        return "plumbing (no traffic)"
    return opcode


NO_TRAFFIC = {"parameter", "constant", "get-tuple-element", "tuple",
              "bitcast"}


def breakdown(hlo_text: str, top_n: int = 30):
    sizes = {}     # instr name -> output bytes
    rows = []
    for name, shape_str, opcode, operands, ln in parse_entry(hlo_text):
        out_b = shape_bytes(shape_str)
        sizes[name] = out_b
        if opcode in NO_TRAFFIC:
            continue
        in_b = sum(sizes.get(op, 0) for op in operands)
        rows.append({
            "name": name,
            "op": opcode,
            "class": classify(opcode, ln),
            "out_mb": round(out_b / 1e6, 2),
            "in_mb": round(in_b / 1e6, 2),
            "total_mb": round((out_b + in_b) / 1e6, 2),
        })
    rows.sort(key=lambda r: -r["total_mb"])
    by_class = defaultdict(lambda: [0.0, 0])
    for r in rows:
        by_class[r["class"]][0] += r["total_mb"]
        by_class[r["class"]][1] += 1
    total = sum(r["total_mb"] for r in rows)
    classes = sorted(
        ({"class": k, "gb": round(v[0] / 1e3, 2), "n_ops": v[1],
          "pct": round(100 * v[0] / total, 1)}
         for k, v in by_class.items()),
        key=lambda c: -c["gb"],
    )
    return {
        "total_estimated_gb": round(total / 1e3, 2),
        "by_class": classes,
        "top_instructions": rows[:top_n],
        "n_entry_instructions": len(rows),
    }


def main(out_path="artifacts/hbm_breakdown_r04.json",
         batch=256, dump_hlo=None):
    import bench

    print("breakdown: compiling step", file=sys.stderr)
    step, state, b, *_ = bench.build_bench(batch, 1)
    text = step.as_text()
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(text)
    art = {"what": __doc__.split("\n")[0], "batch_per_chip": batch}
    art.update(breakdown(text))
    try:
        ca = step.cost_analysis()
        art["xla_cost_analysis_gb"] = round(
            float(ca["bytes accessed"]) / 1e9, 2
        )
    except Exception as e:
        art["xla_cost_analysis_gb"] = None
        art["cost_analysis_error"] = f"{type(e).__name__}: {e}"
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(art, f, indent=2)
    print(f"breakdown: est {art['total_estimated_gb']} GB vs "
          f"cost_analysis {art['xla_cost_analysis_gb']} GB -> {out_path}",
          file=sys.stderr)
    for c in art["by_class"]:
        print(f"breakdown:   {c['pct']:5.1f}%  {c['gb']:7.2f} GB  "
              f"({c['n_ops']:4d} ops)  {c['class']}", file=sys.stderr)


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--out", default="artifacts/hbm_breakdown_r04.json")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--dump-hlo", default=None,
                   help="also write the optimized HLO text here")
    a = p.parse_args()
    main(a.out, a.batch, a.dump_hlo)
