import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on
the production mesh, record memory/cost/collective analysis.

MUST be executed as its own process (`python -m repro.launch.dryrun`) so
the XLA_FLAGS above take effect before jax initializes. Everything else
(tests, benchmarks) sees the real device count.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod both] [--out experiments/dryrun]
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.launch.mesh import make_production_mesh

from repro.launch.analysis import (HBM_BW, ICI_BW, PEAK_FLOPS,  # noqa: E402
                                   collective_bytes)


def _probe_specs(spec):
    """XLA cost analysis counts while/scan bodies ONCE, not x trip-count.
    For depth-scanned families we compile two small *unrolled* probes and
    extrapolate linearly in depth (layers / time steps): exact for
    homogeneous stacks. Returns None when costs are already exact
    (python-loop models)."""
    import dataclasses as dc
    cfg = spec.model_cfg
    if spec.family == "lm":
        lo = dc.replace(spec, model_cfg=dc.replace(cfg, n_layers=2,
                                                   unroll=True))
        hi = dc.replace(spec, model_cfg=dc.replace(cfg, n_layers=3,
                                                   unroll=True))
        return lo, hi, 2, 3, cfg.n_layers
    if spec.family == "recsys":
        lo = dc.replace(spec, model_cfg=dc.replace(cfg, seq_len=4,
                                                   unroll=True))
        hi = dc.replace(spec, model_cfg=dc.replace(cfg, seq_len=8,
                                                   unroll=True))
        return lo, hi, 4, 8, cfg.seq_len
    return None


def _compile_costs(spec, shape, mesh):
    from repro.train.steps import build_bundle
    with mesh:
        compiled = build_bundle(spec, shape, mesh).lower().compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             verbose: bool = True, probes: bool = True) -> dict:
    from repro.train.steps import build_bundle
    spec = registry.get_spec(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rec = {"arch": arch, "shape": shape,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "axes": list(mesh.axis_names), "devices": n_dev}
    t0 = time.perf_counter()
    try:
        with mesh:
            bundle = build_bundle(spec, shape, mesh)
            lowered = bundle.lower()
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)

        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))

        pr = _probe_specs(spec)
        if probes and pr is not None:
            lo_spec, hi_spec, d_lo, d_hi, d_real = pr
            f_lo, b_lo, c_lo = _compile_costs(lo_spec, shape, mesh)
            f_hi, b_hi, c_hi = _compile_costs(hi_spec, shape, mesh)
            scale = (d_real - d_lo) / (d_hi - d_lo)
            flops = f_lo + scale * (f_hi - f_lo)
            bytes_acc = b_lo + scale * (b_hi - b_lo)
            coll = {k: c_lo.get(k, 0) + scale * (c_hi.get(k, 0) -
                                                 c_lo.get(k, 0))
                    for k in set(c_lo) | set(c_hi)}
            rec["probe"] = {"depths": [d_lo, d_hi, d_real],
                            "flops_lo_hi": [f_lo, f_hi],
                            "scan_reported_flops": float(
                                cost.get("flops", 0.0))}
        rec.update(
            ok=True, step=bundle.name,
            lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
            flops_per_device=flops, bytes_per_device=bytes_acc,
            collective_bytes_per_device=coll,
            mem={k: getattr(mem, k, None) for k in
                 ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes")} if mem is not None else None,
            t_compute_s=flops / PEAK_FLOPS,
            t_memory_s=bytes_acc / HBM_BW,
            t_collective_s=coll["total"] / ICI_BW,
        )
        dom = max(("t_compute_s", "t_memory_s", "t_collective_s"),
                  key=lambda k: rec[k])
        rec["dominant"] = dom.replace("t_", "").replace("_s", "")
        if verbose:
            mm = rec["mem"] or {}
            print(f"[{arch}/{shape}/{rec['mesh']}] ok "
                  f"compile={rec['compile_s']}s flops/dev={flops:.3e} "
                  f"bytes/dev={bytes_acc:.3e} coll/dev={coll['total']:.3e} "
                  f"args={mm.get('argument_size_in_bytes')} "
                  f"temp={mm.get('temp_size_in_bytes')} dom={rec['dominant']}")
    except Exception as e:   # record failures — they are bugs to fix
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[{arch}/{shape}/{rec['mesh']}] FAIL {rec['error']}")
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = "multipod" if multi_pod else "singlepod"
    (out_dir / f"{arch}__{shape}__{tag}.json").write_text(
        json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-islabel", action="store_true")
    ap.add_argument("--multipod", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    out = Path(args.out)

    cells = (registry.all_cells(include_islabel=args.include_islabel)
             if args.all else [(args.arch, args.shape)])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.multipod]
    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, mp, out)
            n_fail += 0 if rec.get("ok") else 1
    print(f"dry-run complete: {len(cells) * len(meshes)} cells, "
          f"{n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
