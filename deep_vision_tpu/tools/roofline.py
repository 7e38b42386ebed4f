"""A defensible HBM bound for the flagship step — shape math, not guesses.

Round 3 claimed "92% of HBM roofline" from XLA's `cost_analysis()` "bytes
accessed"; round 4 disqualified that number (at batch 128 it implies
946 GB/s, above the v5e's 819 GB/s pin limit — VMEM-served reads count, so
it over-counts real HBM traffic and cannot anchor a roofline). This tool
replaces it with two defensible quantities:

1. `analytic` — a per-layer activation+param+grad traffic model computed
   from the architecture's shapes alone (this framework knows every conv's
   in/out tensor). The dataflow assumptions are explicit and FUSION-OPTIMAL
   (each tensor crosses HBM the minimum number of times a conv-boundary
   dataflow permits), so the result is a LOWER bound on real traffic: real
   XLA schedules can only move more bytes, never fewer.
2. `measured` (needs the chip) — profiler DMA/copy-event totals over a
   traced window, and the device step time from the "XLA Modules" line
   (bench._device_step_ms method).

The verdict logic is printed and recorded: if `analytic / peak_bw` accounts
for (most of) the device step time, the step is memory-bound and the bound
names the biggest per-layer consumers to attack next; if it does NOT (the
r4 numbers put the fusion-optimal bound well under the 46 ms step), then
"HBM-bound" is unsupported at the optimal-dataflow limit and the gap is
compute/occupancy (MXU utilization of the actual conv shapes) — which is a
different optimization conversation than byte-cutting.

    python -m deep_vision_tpu.tools.roofline --analytic          # no chip
    python -m deep_vision_tpu.tools.roofline --out artifacts/roofline_r05.json
"""
from __future__ import annotations

import argparse
import json
import os
from typing import List, Optional

from deep_vision_tpu.core.backend import device_peaks

# the analytic model is sized for one chip; its peaks come from the one
# table (core/backend.py), keyed by jax's device_kind for a v5e
REFERENCE_DEVICE_KIND = "TPU v5 lite"
PEAK_HBM_GBS = device_peaks(REFERENCE_DEVICE_KIND).hbm_bytes_per_s / 1e9
PEAK_BF16_TFLOPS = device_peaks(REFERENCE_DEVICE_KIND).bf16_flops / 1e12

ACT_BYTES = 2   # activations/activation-grads travel bf16
PAR_BYTES = 4   # params, weight grads, momentum are f32


def resnet50_conv_shapes(image: int = 224, width: int = 64,
                         stem: str = "s2d") -> List[dict]:
    """Every conv in the flagship ResNet-50 (models/resnet.py) as
    {name, h, w, cin, cout, k, stride} — the shape source for the traffic
    and FLOP models. Includes the bottleneck projection (downsample) convs.
    """
    # s2d: host space-to-depth ships (H/2, W/2, 12) and the stem conv is the
    # 4x4 reshaped twin of the 7x7/s2 (models/resnet.py SpaceToDepthStem);
    # either way the stem's output grid is image/2
    stem_args = (dict(cin=12, k=4, stride=1) if stem == "s2d"
                 else dict(cin=3, k=7, stride=2))
    layers = [dict(name="stem", h=image // 2, w=image // 2, cout=width,
                   **stem_args)]
    h = image // 2
    h //= 2  # maxpool /2
    stage_sizes = (3, 4, 6, 3)
    cin = width
    for i, n_blocks in enumerate(stage_sizes):
        feat = width * (2 ** i)
        for j in range(n_blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            hout = h // stride
            pre = f"s{i}b{j}"
            layers.append(dict(name=f"{pre}.conv1", h=h, w=h, cin=cin,
                               cout=feat, k=1, stride=1))
            layers.append(dict(name=f"{pre}.conv2", h=h, w=h, cin=feat,
                               cout=feat, k=3, stride=stride))
            layers.append(dict(name=f"{pre}.conv3", h=hout, w=hout, cin=feat,
                               cout=4 * feat, k=1, stride=1))
            if j == 0:
                layers.append(dict(name=f"{pre}.proj", h=h, w=h, cin=cin,
                                   cout=4 * feat, k=1, stride=stride))
            cin = 4 * feat
            h = hout
    layers.append(dict(name="head", h=1, w=1, cin=cin, cout=1000, k=1,
                       stride=1))
    return layers


def analytic_traffic(batch: int, image: int = 224,
                     stem: str = "s2d") -> dict:
    """Fusion-optimal per-step HBM traffic lower bound, itemized per layer.

    Dataflow model (each line is an explicit assumption, all minimal):
      forward   — conv reads its input once, writes its output once (BN +
                  ReLU + residual-add ride the conv epilogue, as the
                  hbm_breakdown_r04 fusions show; the skip tensor is read
                  once more at the join)
      backward  — reads the saved input once (shared by dgrad and wgrad in
                  an ideal fusion), reads the output grad once, writes the
                  input grad once
      params    — SGD+momentum: weight read fwd + read bwd + grad write +
                  momentum read/write + weight write (6x param bytes)
    Activations bf16, params/grads/momentum f32.
    """
    layers = resnet50_conv_shapes(image, stem=stem)
    rows = []
    total_act = total_par = total_flops = 0
    for L in layers:
        hout, wout = L["h"] // L["stride"], L["w"] // L["stride"]
        a_in = batch * L["h"] * L["w"] * L["cin"] * ACT_BYTES
        a_out = batch * hout * wout * L["cout"] * ACT_BYTES
        # fwd: read in, write out; bwd: read in, read dout, write din
        act = 3 * a_in + 2 * a_out
        p = L["k"] * L["k"] * L["cin"] * L["cout"] * PAR_BYTES
        par = 6 * p
        flops = 2 * batch * hout * wout * L["k"] * L["k"] * L["cin"] * \
            L["cout"] * 3  # fwd + dgrad + wgrad
        rows.append({"layer": L["name"], "gb": round((act + par) / 1e9, 4),
                     "act_gb": round(act / 1e9, 4),
                     "gflops": round(flops / 1e9, 1)})
        total_act += act
        total_par += par
        total_flops += flops
    rows.sort(key=lambda r: -r["gb"])
    total = total_act + total_par
    itemized = sum(r["gb"] for r in rows)
    return {
        "assumptions": analytic_traffic.__doc__.strip().splitlines()[2:],
        "batch": batch,
        "total_gb": round(total / 1e9, 2),
        "itemized_total_gb": round(itemized, 2),  # sum over ALL layers; must
                                                  # equal total_gb
        "activation_gb": round(total_act / 1e9, 2),
        "param_gb": round(total_par / 1e9, 2),
        "train_tflops_per_step": round(total_flops / 1e12, 2),
        "min_step_ms_if_memory_bound": round(total / PEAK_HBM_GBS / 1e6, 2),
        "min_step_ms_if_compute_bound": round(
            total_flops / (PEAK_BF16_TFLOPS * 1e12) * 1e3, 2
        ),
        "top_layers": rows[:10],
    }


def measure_on_chip(batch: int) -> dict:
    """Chip-side: device step time (XLA Modules trace) + DMA-event byte
    totals from the same trace window, per step. Raises if the backend or
    trace is unavailable — callers record the analytic half regardless."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    import shutil
    import tempfile

    import jax
    import numpy as np

    import bench

    step, state, b, batch_size, n_chips, devices = bench.build_bench(batch, 1)
    for _ in range(3):
        state, loss = step(state, b)
    float(loss)

    tmpdir = tempfile.mkdtemp(prefix="dv_roofline_")
    try:
        jax.profiler.start_trace(tmpdir)
        n_steps = 10
        for _ in range(n_steps):
            state, loss = step(state, b)
        float(loss)
        jax.profiler.stop_trace()
        xs = bench.load_xspace(tmpdir)
        module_ms = []
        dma_bytes = 0
        dma_events = 0
        dma_names = {}
        for plane in xs.planes:
            if not plane.name.startswith("/device:TPU"):
                continue
            stat_names = {i: m.name for i, m in plane.stat_metadata.items()}
            ev_names = {i: m.name for i, m in plane.event_metadata.items()}
            for line in plane.lines:
                for ev in line.events:
                    name = ev_names.get(ev.metadata_id, "")
                    if line.name == "XLA Modules":
                        module_ms.append(ev.duration_ps / 1e9)
                        continue
                    size = None
                    for st in ev.stats:
                        sname = stat_names.get(st.metadata_id, "")
                        if "byte" in sname.lower() or "size" in sname.lower():
                            size = (st.uint64_value or st.int64_value)
                    if size:
                        dma_bytes += int(size)
                        dma_events += 1
                        key = name or line.name
                        dma_names[key] = dma_names.get(key, 0) + int(size)
        med_ms = float(np.median(module_ms)) if module_ms else None
        top = sorted(dma_names.items(), key=lambda kv: -kv[1])[:8]
        return {
            "device_kind": devices[0].device_kind,
            "device_step_ms": round(med_ms, 2) if med_ms else None,
            "traced_steps": n_steps,
            "dma_events": dma_events,
            "dma_gb_per_step": round(dma_bytes / n_steps / 1e9, 2)
            if dma_events else None,
            "dma_top_sources_gb": {k: round(v / n_steps / 1e9, 3)
                                   for k, v in top},
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def load_bench_json(path: str) -> dict:
    """Accept either a raw bench.py JSON line/file or a driver BENCH_rNN.json
    wrapper (the flat dict lives under 'parsed')."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict) or "value" not in doc:
        raise ValueError(f"{path}: not a bench result "
                         "(need the bench.py JSON line or a BENCH_rNN.json)")
    return doc


def bench_position(bench: dict, analytic: dict) -> dict:
    """Where the MEASURED step and each analytic layer sit on the roofline,
    anchored to bench.py's own numbers (`hbm_gbytes_per_sec_per_chip`,
    `model_flops_per_image` from xla_cost_analysis, the MFU percentages)
    instead of re-deriving them.

    Per row: arithmetic intensity (flop/byte), the roofline bound at that
    intensity (min(MXU peak, intensity * pin bandwidth)), and achieved-vs-
    bound plus achieved-vs-the-30%-MFU-baseline — the gap this PR's three
    levers (prefetch, multistep, fused kernels) exist to close."""
    ridge = PEAK_BF16_TFLOPS * 1e12 / (PEAK_HBM_GBS * 1e9)  # flop/byte
    rows = []

    def row(name, tflops_achieved, intensity, extra=None):
        bound_tflops = min(PEAK_BF16_TFLOPS,
                           intensity * PEAK_HBM_GBS / 1e3)
        r = {
            "name": name,
            "intensity_flop_per_byte": round(intensity, 1),
            "bound": "compute" if intensity >= ridge else "memory",
            "roofline_tflops": round(bound_tflops, 1),
        }
        if tflops_achieved is not None:
            r["achieved_tflops"] = round(tflops_achieved, 1)
            r["pct_of_roofline"] = round(
                100 * tflops_achieved / bound_tflops, 1)
            r["vs_30pct_mfu_baseline"] = round(
                tflops_achieved / (0.30 * PEAK_BF16_TFLOPS), 2)
        if extra:
            r.update(extra)
        return r

    flops_per_image = bench.get("model_flops_per_image")  # GF, cost analysis
    gbs = bench.get("hbm_gbytes_per_sec_per_chip")
    for kind, rate_key, mfu_key in (
            ("wall", "value", "mfu_wall_pct"),
            ("device", "device_images_per_sec_per_chip", "mfu_device_pct")):
        rate = bench.get(rate_key)
        if not rate or not flops_per_image:
            continue
        achieved = rate * flops_per_image / 1e3  # TFLOP/s
        # intensity from the bench's own cost-analysis bytes (an HBM upper
        # bound — VMEM-served reads count — so the intensity is a LOWER
        # bound and the memory-bound verdict conservative; bench.py NB)
        gb_per_step = bench.get("hbm_gbytes_per_step_per_chip")
        bpc = bench.get("batch_per_chip") or 1
        intensity = (flops_per_image * bpc / gb_per_step
                     if gb_per_step else ridge)
        rows.append(row(
            f"train_step ({kind})", achieved, intensity,
            {"images_per_sec_per_chip": rate,
             "mfu_pct": bench.get(mfu_key)}))
    # per-layer placement from the analytic shape model: no achieved rate
    # per layer (the profile has no per-op split on this backend), but the
    # intensity says which kernels even CAN go fast — the low-intensity
    # rows are the fusion targets (fewer HBM bytes), the high ones the
    # MXU-occupancy targets
    for layer in analytic.get("top_layers", []):
        if layer.get("gb"):
            rows.append(row(layer["layer"], None,
                            layer["gflops"] / layer["gb"]))
    return {
        "peak_tflops": PEAK_BF16_TFLOPS,
        "peak_hbm_gbs": PEAK_HBM_GBS,
        "ridge_flop_per_byte": round(ridge, 1),
        "baseline_mfu_pct": 30.0,
        "bench_source": {k: bench.get(k) for k in (
            "metric", "value", "vs_baseline", "multistep",
            "mfu_wall_pct", "mfu_device_pct", "flops_source")},
        "rows": rows,
    }


def render_roofline(pos: dict) -> str:
    lines = [
        f"roofline: peak {pos['peak_tflops']:.0f} TF/s, "
        f"{pos['peak_hbm_gbs']:.0f} GB/s, ridge "
        f"{pos['ridge_flop_per_byte']:.0f} flop/B "
        f"(baseline = {pos['baseline_mfu_pct']:.0f}% MFU)"
    ]
    for r in pos["rows"]:
        s = (f"  {r['name']:<24} {r['intensity_flop_per_byte']:>8.1f} f/B "
             f"{r['bound']:<7} roof {r['roofline_tflops']:>6.1f} TF/s")
        if "achieved_tflops" in r:
            s += (f"  achieved {r['achieved_tflops']:>6.1f} TF/s "
                  f"({r['pct_of_roofline']:.0f}% of roof, "
                  f"{r['vs_30pct_mfu_baseline']:.2f}x the 30%-MFU baseline)")
        lines.append(s)
    return "\n".join(lines)


def verdict(analytic: dict, measured: Optional[dict]) -> str:
    mem_ms = analytic["min_step_ms_if_memory_bound"]
    mxu_ms = analytic["min_step_ms_if_compute_bound"]
    if not measured or not measured.get("device_step_ms"):
        return (f"analytic-only: fusion-optimal traffic "
                f"{analytic['total_gb']} GB needs >= {mem_ms} ms at "
                f"{PEAK_HBM_GBS:.0f} GB/s; MXU floor {mxu_ms} ms — "
                "measured step time required for the binding verdict")
    dev = measured["device_step_ms"]
    frac_mem = mem_ms / dev
    frac_mxu = mxu_ms / dev
    dma = measured.get("dma_gb_per_step")
    dma_gbs = dma / dev * 1e3 if dma else None  # measured bandwidth
    parts = [
        f"device step {dev} ms vs memory-bound floor {mem_ms} ms "
        f"({100 * frac_mem:.0f}% of step) and MXU floor {mxu_ms} ms "
        f"({100 * frac_mxu:.0f}%)"
    ]
    if dma:
        parts.append(
            f"measured DMA traffic {dma} GB/step = {dma_gbs:.0f} GB/s "
            f"({100 * dma_gbs / PEAK_HBM_GBS:.0f}% of pin bw)"
        )
    if frac_mem >= 0.8:
        parts.append("VERDICT: memory-bound at the fusion-optimal limit — "
                     "byte-cutting (layout, dtype, recompute) is the lever")
    elif dma and dma_gbs >= 0.8 * PEAK_HBM_GBS:
        parts.append("VERDICT: memory-bound via measured traffic (real "
                     "schedule moves more bytes than the optimal-dataflow "
                     "bound) — close the gap between measured and bound")
    else:
        parts.append("VERDICT: NOT memory-bound at these numbers — the gap "
                     "to both floors is MXU utilization / occupancy of the "
                     "actual conv shapes (early high-res low-channel convs "
                     "tile poorly), not bandwidth")
    return "; ".join(parts)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--batch", type=int, default=128,
                   help="per-chip batch (the flagship bench point)")
    p.add_argument("--analytic", action="store_true",
                   help="skip the chip: shape-math bound only")
    p.add_argument("--device-ms", type=float, default=None,
                   help="previously measured device step time (ms) to use "
                        "when the chip is unreachable; cite --device-ms-source")
    p.add_argument("--device-ms-source", default=None,
                   help="artifact the --device-ms number came from")
    p.add_argument("--bench-json", default=None, metavar="PATH",
                   help="anchor the roofline to a measured bench result "
                        "(bench.py JSON line or a driver BENCH_rNN.json): "
                        "renders where the step and each analytic layer "
                        "sit vs the 30%%-MFU baseline")
    p.add_argument("--out", default="artifacts/roofline_r05.json")
    args = p.parse_args(argv)

    bench = None
    if args.bench_json:
        bench = load_bench_json(args.bench_json)
        if bench.get("batch_per_chip"):
            args.batch = int(bench["batch_per_chip"])
    analytic = analytic_traffic(args.batch)
    measured = None
    if not args.analytic:
        try:
            measured = measure_on_chip(args.batch)
        except Exception as e:
            measured = {"error": f"{type(e).__name__}: {e}"}
    if (measured is None or "error" in measured) and args.device_ms:
        prior = {"device_step_ms": args.device_ms,
                 "source": args.device_ms_source or "prior measurement",
                 "note": "chip unreachable; device time from the cited "
                         "prior artifact (no DMA totals this run)"}
        if measured and "error" in measured:
            prior["chip_error"] = measured["error"]
        measured = prior
    v = verdict(analytic, measured if measured and "error" not in
                (measured or {}) else None)
    result = {
        "what": "HBM roofline re-founded: fusion-optimal analytic traffic "
                "bound (shape math) + profiler DMA totals; replaces the "
                "disqualified cost_analysis() bytes (see bench.py NB)",
        "peak_hbm_gbs": PEAK_HBM_GBS,
        "peak_bf16_tflops": PEAK_BF16_TFLOPS,
        "analytic": analytic,
        "measured": measured,
        "verdict": v,
        # the measured optimization attempts behind the current operating
        # point (interleaved same-process A/B unless noted):
        "optimization_attempts": [
            {"lever": "batch size (coarse sweep 128-512)",
             "result": "WIN: 97.88 -> 46.31 ms per 128 images "
                       "(2615 -> 2764 img/s); batch 128 is the knee",
             "artifact": "artifacts/batch_scaling_r04.json"},
            {"lever": "Layout.AUTO input/param layouts",
             "result": "NULL: bytes-accessed 77.9 -> 68.1 GB but device "
                       "time 97.9 -> 103.4 ms — XLA's default layout "
                       "copies buy conv-optimal tiling worth more than "
                       "their bandwidth",
             "artifact": "artifacts/layout_probe_r04.json"},
            {"lever": "compiler knobs (rwb fusion, latency-hiding "
                      "scheduler, scoped vmem, MSA)",
             "result": "NULL: none beat baseline in interleaved A/B (r3)",
             "artifact": "memory: r3 probe series"},
            {"lever": "fused single-pass BatchNorm",
             "result": "WIN (shipped): 1.286x step vs flax nn.BatchNorm",
             "artifact": "artifacts/ablate_r04.json"},
        ],
    }
    if bench is not None:
        pos = bench_position(bench, analytic)
        result["bench_roofline"] = pos
        print(render_roofline(pos))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(v)
    print(f"-> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
