"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Terms (per (arch, shape, mesh) cell), with the peaks of the device kind the
cell targets (:data:`DEVICE_PEAKS`; an unknown kind is an error):

    compute_s    = FLOPs_per_device / peak_flops        (bf16 peak per chip)
    memory_s     = bytes_per_device / hbm_bw            (HBM bandwidth)
    collective_s = collective_bytes_per_device / link_bw   (per ICI link)

``compiled.cost_analysis()`` is evaluated on the SPMD-partitioned per-device
module, so its flops/bytes are already per-device; dividing by per-chip peak
gives the same number as total/(chips x peak) in the assignment formula.
Collective bytes are not in cost_analysis: we parse the post-optimization
HLO and sum operand bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.  MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D
(MoE); the ratio MODEL_FLOPS / HLO_FLOPs flags remat/dispatch waste.
"""
from __future__ import annotations

import re
from typing import Any


_V5E = {
    "flops": 197e12,     # bf16 FLOP/s per chip
    "hbm_bw": 819e9,     # HBM bytes/s per chip
    "link_bw": 50e9,     # bytes/s per ICI link (1,600 Gbit/s over 4 links)
    "source": 'Google Cloud documentation, "TPU v5e"',
}

#: Published per-chip peaks keyed by ``jax.Device.device_kind``, with source.
DEVICE_PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; no default for other kinds."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, Any]:
    """Sum operand bytes per collective kind from post-optimization HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        kind = None
        for k in _COLLECTIVES:
            # match op name after '=' to avoid matching variable names
            if re.search(rf"=\s*(\([^)]*\)\s*)?[a-z0-9\[\],{{}} ]*{k}(-start|-done)?\(", s):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done" in s:
            continue  # operands counted at -start
        # operand shapes are inside the call parens; result shape precedes '='
        lhs, _, rhs = s.partition("=")
        m = re.search(rf"{kind}(?:-start)?\((.*)\)\s*(,|$)", rhs)
        args = m.group(1) if m else rhs
        bytes_ = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(args))
        out[kind] += bytes_
        counts[kind] += 1
    total = sum(out.values())
    return {"per_kind_bytes": out, "per_kind_count": counts, "total_bytes": total}


def tick_step_roofline(s: int, j: int, w: int, *, device_kind: str,
                       dtype_bytes: int = 4) -> dict:
    """Analytic roofline for one fused tick-step invocation
    (:mod:`repro.kernels.tick_step`) at geometry ``[S, J]`` × ``W`` workers.

    Traffic model (HBM side, one invocation): the kernel streams the share
    table, queue counts, and the ``[S, J, W]`` ring window in once, and the
    selections/pops out once — the queue state itself stays resident in VMEM
    scratch across the W draws, which is the point of the fusion:

        bytes  = S·J·(3 + W)·dtype_bytes  in   (shares, qcount, window)
               + S·W·2·dtype_bytes        in   (free, u)
               + S·(3·W + 2·J)·dtype_bytes out (sel, valid, demand_any,
                                                qcount', pops)

    Per draw the select is a masked renorm + prefix sum + segment count over
    J lanes (≈ 8 ops/lane incl. the fallback branch) plus the pop update
    (≈ 4 ops/lane), so flops ≈ S·W·J·12.  At simulation geometry (J ≤ a few
    thousand) arithmetic intensity is far below the machine balance point
    (~240 flops/byte on v5e), so the kernel is **memory-bound** and the
    per-tick budget is the HBM streaming time — that is the bytes/flop
    justification behind the ``kern_tick_budget_*`` rows in BENCH_kern.json:
    a fused tick is allowed its own traffic at HBM speed, nothing more.
    """
    bytes_in = s * j * (3 + w) * dtype_bytes + s * w * 2 * dtype_bytes
    bytes_out = s * (3 * w + 2 * j) * dtype_bytes
    bytes_total = bytes_in + bytes_out
    flops = s * w * j * 12.0
    peaks = device_peaks(device_kind)
    memory_s = bytes_total / peaks["hbm_bw"]
    compute_s = flops / peaks["flops"]
    return {
        "s": s, "j": j, "w": w,
        "bytes": bytes_total,
        "flops": flops,
        "intensity_flops_per_byte": flops / bytes_total,
        "memory_s": memory_s,
        "compute_s": compute_s,
        "bound": "memory" if memory_s >= compute_s else "compute",
        "budget_us": max(memory_s, compute_s) * 1e6,
    }


def model_flops(cfg, shape) -> float:
    """6·N·D with N = active params (MoE counts top-k experts only)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def analyze_compiled(cfg, shape, compiled, chips: int, *,
                     device_kind: str) -> dict:
    from .hlo_parse import analyze_hlo

    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
    except Exception:
        ca = {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    # loop-aware accounting (cost_analysis counts while bodies once; our
    # scanned-layer models would be undercounted by the trip count)
    acc = analyze_hlo(hlo) if hlo else {}
    flops = float(acc.get("flops", 0.0)) or float(ca.get("flops", 0.0))
    bytes_acc = (float(acc.get("bytes_accessed", 0.0))
                 or float(ca.get("bytes accessed", 0.0)))
    coll = {
        "per_kind_bytes": acc.get("collective_bytes", {}),
        "per_kind_count": acc.get("collective_count", {}),
        "total_bytes": acc.get("collective_total_bytes", 0.0),
    }

    peaks = device_peaks(device_kind)
    compute_s = flops / peaks["flops"]
    memory_s = bytes_acc / peaks["hbm_bw"]
    collective_s = coll["total_bytes"] / peaks["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    mf_per_device = mf / chips
    useful_ratio = mf_per_device / flops if flops else 0.0
    # roofline fraction: useful model flops per device per bound-step-time
    step_time = max(terms.values())
    roofline_frac = ((mf_per_device / peaks["flops"]) / step_time
                     if step_time else 0.0)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception:
        pass

    return {
        "chips": chips,
        "flops_per_device": flops,
        "flops_cost_analysis_raw": float(ca.get("flops", 0.0)),
        "bytes_per_device": bytes_acc,
        "collectives": coll,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops_total": mf,
        "useful_flops_ratio": useful_ratio,
        "roofline_fraction": roofline_frac,
        "memory_analysis": mem,
        "cost_analysis_keys": sorted(ca)[:40] if ca else [],
    }
