import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import (device count locks on first init).

"""Multi-pod dry-run: lower + compile every (arch × input shape) on the
production mesh and report memory / cost / collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
      --shape train_4k [--multi-pod] [--all] [--json out.json]
"""
import argparse
import json
import sys
import time

import jax

from repro.configs import get_config, list_configs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import SHAPES, build_step, shape_applicable
from repro.utils import hlo as hlo_util
from repro.utils.flops import model_flops_6nd


def _scan_corrected_cost(cfg, mesh, shape, step_kw):
    """XLA cost_analysis counts while-loop bodies ONCE (verified with a
    controlled scan-of-matmuls), so scanned configs under-report flops /
    bytes / collectives by the trip count. Correction: lower two reduced-
    depth UNROLLED variants at full tensor shapes and extrapolate the
    per-layer marginal cost linearly to the full depth. Marginal layers
    sit server-side of the split (split=1) — the same math at the same
    shapes, so the linear model is exact up to embed/head constants."""
    import dataclasses

    from repro.utils.hlo import collective_bytes as coll_fn

    def make(L):
        return dataclasses.replace(
            cfg, n_layers=L, block_pattern=cfg.block_pattern[:L],
            ffn_pattern=cfg.ffn_pattern[:L], scan_layers=False)

    from repro.models.transformer import _segments
    segs = _segments(cfg, 0, cfg.n_layers)
    prefix = max(segs, key=lambda s: s[1] - s[0])[0]
    L1, L2 = prefix + 2, prefix + 4
    pts = []
    for L in (L1, L2):
        c = make(L)
        kw = dict(step_kw)
        if SHAPES[shape]["kind"] == "train":
            kw["split"] = min(kw.get("split") or 1, 1) or 1
        step, in_sh, out_sh, (pa, ba) = build_step(c, mesh, shape, **kw)
        with mesh:
            compiled = jax.jit(step, in_shardings=in_sh,
                               out_shardings=out_sh).lower(pa, ba).compile()
        cost = compiled.cost_analysis()
        pts.append((float(cost.get("flops", 0.0)),
                    float(cost.get("bytes accessed", 0.0)),
                    float(coll_fn(compiled.as_text())["_total"])))
    dL = L2 - L1
    out = []
    for i in range(3):
        slope = (pts[1][i] - pts[0][i]) / dL
        out.append(pts[0][i] + slope * (cfg.n_layers - L1))
    return tuple(out)          # (flops, bytes, coll_bytes) per chip


def dryrun_one(arch: str, shape: str, *, multi_pod: bool = False,
               verbose: bool = True, **step_kw):
    cfg = get_config(arch)
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape, "skipped": True,
                "reason": "long-context not applicable (full attention)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    step, in_sh, out_sh, (params_abs, batch_abs) = build_step(
        cfg, mesh, shape, **step_kw)
    donate = (0,) if SHAPES[shape]["kind"] == "train" else ()
    if SHAPES[shape]["kind"] == "decode":
        donate = (1,)                       # decode donates the caches
    with mesh:
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(params_abs, batch_abs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    s = SHAPES[shape]
    n_tokens = s["batch"] * (s["seq"] if s["kind"] != "decode" else 1)
    mf = model_flops_6nd(cfg, n_tokens)
    if s["kind"] != "train":
        mf /= 3.0                                  # fwd only (no bwd)
    roof = hlo_util.analyze(compiled, arch=arch, shape=shape,
                            n_chips=n_chips, model_flops=mf)
    estimated = False
    if cfg.scan_layers and s["kind"] == "train":
        # while-loop bodies are cost-counted once; extrapolate (see above)
        fl, by, cb = _scan_corrected_cost(cfg, mesh, shape, step_kw)
        roof.hlo_flops, roof.hlo_bytes, roof.coll_bytes = fl, by, cb
        estimated = True
    rec = roof.row()
    rec["flops_estimated"] = estimated
    rec.update({
        "multi_pod": multi_pod,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        "coll_counts": roof.coll_detail.get("_counts"),
    })
    if verbose:
        print(f"== {arch} × {shape} ({'multi' if multi_pod else 'single'}"
              f"-pod, {n_chips} chips) ==")
        print("memory_analysis:", mem)
        print("cost_analysis: flops=%.3e bytes=%.3e" %
              (rec["hlo_flops"], rec["hlo_bytes"]))
        print("collectives:", rec["coll_counts"],
              "bytes=%.3e" % rec["coll_bytes"])
        print("roofline: compute=%.4fs memory=%.4fs collective=%.4fs "
              "dominant=%s useful=%.2f" %
              (rec["t_compute_s"], rec["t_memory_s"], rec["t_collective_s"],
               rec["dominant"], rec["useful_ratio"]))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all (arch × shape) pairs")
    ap.add_argument("--split", type=int, default=None)
    ap.add_argument("--groups", type=int, default=None)
    ap.add_argument("--remat-policy", default=None, choices=["dots"],
                    help="selective remat (train shapes)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    lm_archs = [a for a in list_configs()
                if getattr(get_config(a), "arch_type", "cnn") != "cnn"]
    pairs = []
    if args.all:
        pairs = [(a, s) for a in lm_archs for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        pairs = [(args.arch, args.shape)]

    kw = {}
    if args.split is not None:
        kw["split"] = args.split
    if args.groups is not None:
        kw["n_groups"] = args.groups
    if args.remat_policy is not None:
        kw["remat_policy"] = args.remat_policy

    out = []
    for arch, shape in pairs:
        skw = dict(kw) if SHAPES[shape]["kind"] == "train" else {}
        try:
            rec = dryrun_one(arch, shape, multi_pod=args.multi_pod, **skw)
        except Exception as e:                       # noqa: BLE001
            rec = {"arch": arch, "shape": shape, "error": repr(e)[:500]}
            print(f"!! {arch} × {shape} FAILED: {rec['error']}",
                  file=sys.stderr)
        out.append(rec)
        if args.json:                    # incremental: crash-safe
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1, default=str)
    n_err = sum(1 for r in out if "error" in r)
    print(f"\n{len(out)} pairs, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
