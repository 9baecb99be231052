import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
# Must precede any jax import (same contract as dryrun.py).

"""Roofline analysis per (arch x shape) on the single-pod mesh.

Three terms, all **per device** (the compiled module after SPMD
partitioning is the per-device program, so ``cost_analysis()`` and the
collective parse are already per-chip):

    compute    = HLO_FLOPs / peak_FLOP/s        (197e12, bf16 v5e)
    memory     = HLO_bytes / HBM_bw             (819e9 B/s)
    collective = collective_operand_bytes / ICI (50e9 B/s per link)

**Depth extrapolation.** XLA's cost analysis counts a while-loop body
once, and fully unrolling an 88-layer model on this 1-core container
takes ~10 min/cell. Instead we compile the *unrolled* program at two
small depths (L0, L1) — every cost is exactly affine in depth
(homogeneous layer stacks; params, grad all-reduce, optimizer update all
affine in L) — and extrapolate to the real depth:

    f(L) = f(L0) + (f(L1) - f(L0)) / (L1 - L0) * (L - L0)

For structured stacks the depth unit is one *period* (gemma3: 6-layer
local/global cycle; zamba2: one shared+6-mamba group). The extrapolation
is validated against a full-depth unrolled compile in
``tests/test_roofline.py`` (qwen3: <2%% error).

Residual known undercount: the blockwise-attention kv scan is partially
unrolled (cap 32 blocks), so ``long_500k`` decode attention FLOPs are
counted at 32/512 of true — decode cells are memory-bound by orders of
magnitude, so the dominant term is unaffected; the MODEL_FLOPS column
flags it.
"""

import argparse
import dataclasses
import json
from typing import Dict, List, Optional, Tuple

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.launch.mesh import PEAKS, V5E

_V5E = PEAKS[V5E]

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "roofline")


# ---------------------------------------------------------------------------
# depth schedule
# ---------------------------------------------------------------------------

def depth_points(cfg) -> Tuple[int, int, int]:
    """(L0, L1, L_full) in layers, respecting the structural period."""
    if cfg.local_global:                      # gemma3: 6-layer cycle
        p = cfg.local_global + 1
        return p, 2 * p, cfg.n_layers
    if cfg.shared_attn_every:                 # zamba2: 6-mamba groups
        p = cfg.shared_attn_every
        return p, 2 * p, cfg.n_layers
    return 4, 8, cfg.n_layers


def _extract(rec: Dict) -> Dict[str, float]:
    c = rec["cost"]
    return {
        "flops": float(c.get("flops", 0.0)),
        "bytes": float(c.get("bytes accessed", 0.0)),
        "transcendentals": float(c.get("transcendentals", 0.0)),
        "coll_bytes": float(rec["collectives"]["total_bytes"]),
        "temp_bytes": float(rec["memory"].get("temp_size_in_bytes", 0)),
        "arg_bytes": float(rec["memory"].get("argument_size_in_bytes", 0)),
    }


def extrapolate(f0: Dict[str, float], f1: Dict[str, float],
                l0: int, l1: int, l: int) -> Dict[str, float]:
    out = {}
    for k in f0:
        slope = (f1[k] - f0[k]) / (l1 - l0)
        out[k] = f0[k] + slope * (l - l0)
    return out


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def model_flops(cfg, shape, n_devices: int, params: Dict[str, float]
                ) -> float:
    """Useful FLOPs per device per step: 6·N·D train, 2·N·D inference
    (N = active non-embedding params, D = tokens this step)."""
    n = params["body_active"]
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        mult = 6.0
    elif shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        mult = 2.0
    else:                                    # decode: one token per row
        d = shape.global_batch
        mult = 2.0
    return mult * n * d / n_devices


def analyze_cell(arch: str, shape_name: str, *, mesh: str = "single",
                 rule_overrides=(), cfg_overrides: Optional[Dict] = None
                 ) -> Dict[str, object]:
    """Two reduced-depth unrolled compiles -> extrapolated roofline terms."""
    from repro.launch.dryrun import run_cell   # sets XLA_FLAGS on import

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    if not cfg.shape_supported(shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh,
                "status": "skip"}
    l0, l1, lf = depth_points(cfg)
    base_over = dict(cfg_overrides or {})
    # Cost is microbatch-count invariant (same total tokens per step), but
    # unrolling a 16-deep grad-accum loop multiplies compile time ~16x;
    # compile the cost build with n_mb=1 (memory comes from the
    # production scan build in §Dry-run, which keeps the real n_mb).
    base_over.setdefault("microbatch_seq_tokens", 1 << 62)
    rec0 = run_cell(arch, shape_name, mesh, unroll=True,
                    cfg_overrides={**base_over, "n_layers": l0},
                    rule_overrides=rule_overrides)
    rec1 = run_cell(arch, shape_name, mesh, unroll=True,
                    cfg_overrides={**base_over, "n_layers": l1},
                    rule_overrides=rule_overrides)
    f = extrapolate(_extract(rec0), _extract(rec1), l0, l1, lf)

    n_dev = rec0["n_devices"]
    # param counts at FULL depth (cheap, no compile)
    from repro.launch.specs import model_param_counts
    params = model_param_counts(cfg)

    terms = {
        "compute_s": f["flops"] / _V5E.flops_bf16,
        "memory_s": f["bytes"] / _V5E.hbm_bw,
        "collective_s": f["coll_bytes"] / _V5E.ici_bw,
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape, n_dev, params)
    bound_s = max(terms.values())
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh, "status": "ok",
        "kind": shape.kind, "n_devices": n_dev,
        "depths": [l0, l1, lf],
        "hlo_flops": f["flops"], "hlo_bytes": f["bytes"],
        "collective_bytes": f["coll_bytes"],
        "terms_seconds": terms,
        "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": (mf / f["flops"]) if f["flops"] else 0.0,
        "roofline_fraction": (
            (mf / _V5E.flops_bf16) / bound_s if bound_s else 0.0),
        "params": params,
        "compile_seconds": rec0["compile_seconds"] + rec1["compile_seconds"],
        "suggestion": _suggest(dominant, terms, shape),
    }
    return rec


def _suggest(dominant: str, terms: Dict[str, float], shape) -> str:
    c, m, k = (terms["compute_s"], terms["memory_s"],
               terms["collective_s"])
    if dominant == "compute_s":
        return ("compute-bound: cut remat recompute / cast accumulations "
                "to bf16; beyond that this cell is at the FLOP roofline")
    if dominant == "memory_s":
        if shape.kind == "decode":
            return ("HBM-bound (weight+cache streaming): shrink the KV/state"
                    " working set (wider batch amortizes weights; quantize "
                    "cache; window/local layers skip far blocks)")
        return ("HBM-bound: fuse attention (Pallas flash path), bigger "
                "matmul tiles, avoid f32 round-trips on the residual")
    return ("collective-bound: reshard (move TP off the hot axis), overlap "
            "collectives with compute, int8-compress cross-pod grads")


def save_record(rec: Dict[str, object], out_dir: str = OUT_DIR) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


# ---------------------------------------------------------------------------
# table generation (EXPERIMENTS.md §Roofline)
# ---------------------------------------------------------------------------

def markdown_table(records: List[Dict]) -> str:
    head = ("| arch | shape | compute s | memory s | collective s | "
            "dominant | useful/HLO | roofline frac |\n"
            "|---|---|---|---|---|---|---|---|\n")
    rows = []
    for r in sorted(records, key=lambda r: (r["arch"], r["shape"])):
        if r.get("status") != "ok":
            rows.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                        f"skip | — | — |")
            continue
        t = r["terms_seconds"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.3e} | "
            f"{t['memory_s']:.3e} | {t['collective_s']:.3e} | "
            f"{r['dominant'].replace('_s', '')} | "
            f"{r['useful_flops_ratio']:.2f} | "
            f"{r['roofline_fraction']:.2%} |")
    return head + "\n".join(rows) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args(argv)
    archs = ARCH_IDS if args.arch == "all" else (args.arch,)
    shapes = tuple(SHAPES) if args.shape == "all" else (args.shape,)
    recs = []
    for arch in archs:
        for shape in shapes:
            try:
                rec = analyze_cell(arch, shape)
            except Exception as e:
                import traceback
                rec = {"arch": arch, "shape": shape, "mesh": "single",
                       "status": "error", "error": str(e),
                       "traceback": traceback.format_exc()}
                print(f"[FAIL] {arch} x {shape}: {e}")
            save_record(rec, args.out)
            recs.append(rec)
            if rec["status"] == "ok":
                t = rec["terms_seconds"]
                print(f"[ok] {arch} x {shape}: "
                      f"C={t['compute_s']:.2e}s M={t['memory_s']:.2e}s "
                      f"K={t['collective_s']:.2e}s -> {rec['dominant']} "
                      f"(useful {rec['useful_flops_ratio']:.2f}, "
                      f"roofline {rec['roofline_fraction']:.1%})")
    print()
    print(markdown_table(recs))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
