import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Performance hillclimbing driver (§Perf): compile named VARIANTS of a
cell and record the roofline-term deltas.

  PYTHONPATH=src python -m repro.launch.perf --cell qwen2-72b:train_4k
  PYTHONPATH=src python -m repro.launch.perf --cell qwen2-moe:train_4k:mp
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.launch.analysis import (HBM_BW, ICI_BW, PEAK_FLOPS,
                                   collective_bytes)
from repro.launch.dryrun import _compile_costs, _probe_specs
from repro.launch.mesh import make_production_mesh

# variant = (model_cfg field overrides, bundle overrides, spec overrides)
VARIANTS = {
    "qwen2-72b:train_4k": {
        "baseline": ({}, {}, {}),
        "iota_ce": ({"ce_impl": "iota"}, {}, {}),
        "iota+accum4": ({"ce_impl": "iota"}, {"grad_accum": 4}, {}),
        "iota+accum8": ({"ce_impl": "iota"}, {"grad_accum": 8}, {}),
        "iota+accum4+actshard": ({"ce_impl": "iota", "act_shard": True},
                                 {"grad_accum": 4}, {}),
        # with temp headroom from accum+actshard, buy back the remat
        # recompute (saves ~2ND fwd flops + its traffic)
        "accum8+actshard+dots": ({"ce_impl": "iota", "act_shard": True,
                                  "remat_policy": "dots"},
                                 {"grad_accum": 8}, {}),
        "accum8+actshard+noremat": ({"ce_impl": "iota", "act_shard": True,
                                     "remat": False},
                                    {"grad_accum": 8}, {}),
    },
    "qwen2-moe-a2.7b:train_4k:mp": {
        "baseline": ({}, {}, {}),
        "iota_ce": ({"ce_impl": "iota"}, {}, {}),
        "disp_shard": ({"moe": {"dispatch_shard": True}}, {}, {}),
        "disp_shard+cf1": ({"moe": {"dispatch_shard": True,
                                    "capacity_factor": 1.0}}, {}, {}),
        "disp_shard+accum4": ({"moe": {"dispatch_shard": True}},
                              {"grad_accum": 4}, {}),
        # pad 60 -> 64 experts: true EP over the model axis (local expert
        # GEMMs; dispatch becomes all-to-all instead of buffer all-reduce)
        "ep_pad64": ({"moe": {"ep_pad": 64}}, {}, {}),
        "ep_pad64+accum4": ({"moe": {"ep_pad": 64}}, {"grad_accum": 4}, {}),
        "ep_pad64+scatter": ({"moe": {"ep_pad": 64,
                                      "combine_impl": "scatter"}}, {}, {}),
        # int8_pods (shard_map over pod + auto axes) hits an XLA SPMD
        # partitioner CHECK-failure at 512 devices (b/433785288-class);
        # the compression path is validated at 8 devices in
        # tests/test_distributed.py instead.
    },
    "kimi-k2-1t-a32b:train_4k:mp": {
        "baseline": ({}, {}, {}),
        "iota_ce": ({"ce_impl": "iota"}, {}, {}),
        "iota+accum4": ({"ce_impl": "iota"}, {"grad_accum": 4}, {}),
        "iota+accum4+actshard": ({"ce_impl": "iota", "act_shard": True},
                                 {"grad_accum": 4}, {}),
    },
    "islabel:serve_128m": {
        "baseline": ({}, {}, {}),
        "chunked_relax": ({}, {"relax_chunks": 64}, {}),
        "bf16_labels": ({}, {"lbl_dtype": "bfloat16"}, {}),
        "chunked+bf16": ({}, {"relax_chunks": 64,
                              "lbl_dtype": "bfloat16"}, {}),
        "chunked+bf16+r6": ({}, {"relax_chunks": 64,
                                 "lbl_dtype": "bfloat16",
                                 "relax_rounds": 6}, {}),
        "chunked256": ({}, {"relax_chunks": 256}, {}),
        "chunked1024": ({}, {"relax_chunks": 1024}, {}),
    },
    "dimenet:ogb_products": {
        "baseline": ({}, {}, {}),
    },
}


def run_variant(arch, shape, multi_pod, model_over, bundle_over, spec_over,
                name, out_dir: Path):
    from repro.train.steps import build_bundle
    spec = registry.get_spec(arch)
    if model_over:
        mo = dict(model_over)
        cfg = spec.model_cfg
        if "moe" in mo:                       # nested MoE overrides
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, **mo.pop("moe")))
        spec = dataclasses.replace(
            spec, model_cfg=dataclasses.replace(cfg, **mo))
    if spec_over:
        spec = dataclasses.replace(spec, **spec_over)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape, "variant": name,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "model_over": model_over, "bundle_over": bundle_over}
    try:
        t0 = time.perf_counter()
        with mesh:
            compiled = build_bundle(spec, shape, mesh,
                                    overrides=bundle_over).lower().compile()
        cost = compiled.cost_analysis()
        mem = compiled.memory_analysis()
        coll = collective_bytes(compiled.as_text())
        flops = float(cost.get("flops", 0.0))
        byts = float(cost.get("bytes accessed", 0.0))
        pr = _probe_specs(spec)
        if pr is not None:
            lo, hi, d_lo, d_hi, d_real = pr

            probe_over = dict(bundle_over, accum_unroll=True)

            def _with(s):
                from repro.train.steps import build_bundle as bb
                with mesh:
                    c = bb(s, shape, mesh, overrides=probe_over) \
                        .lower().compile()
                return (float(c.cost_analysis().get("flops", 0)),
                        float(c.cost_analysis().get("bytes accessed", 0)),
                        collective_bytes(c.as_text()))
            f_lo, b_lo, c_lo = _with(lo)
            f_hi, b_hi, c_hi = _with(hi)
            sc = (d_real - d_lo) / (d_hi - d_lo)
            flops = f_lo + sc * (f_hi - f_lo)
            byts = b_lo + sc * (b_hi - b_lo)
            coll = {k: c_lo.get(k, 0) + sc * (c_hi.get(k, 0) - c_lo.get(k, 0))
                    for k in set(c_lo) | set(c_hi)}
        rec.update(
            ok=True, compile_s=round(time.perf_counter() - t0, 1),
            flops_per_device=flops, bytes_per_device=byts,
            collective_bytes_per_device=coll,
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            arg_bytes=getattr(mem, "argument_size_in_bytes", None),
            t_compute_s=flops / PEAK_FLOPS, t_memory_s=byts / HBM_BW,
            t_collective_s=coll["total"] / ICI_BW)
        dom = max(("t_compute_s", "t_memory_s", "t_collective_s"),
                  key=lambda k: rec[k])
        rec["dominant"] = dom.replace("t_", "").replace("_s", "")
        print(f"[{name}] temp={rec['temp_bytes']} "
              f"t_mem={rec['t_memory_s']:.2f} t_coll={rec['t_collective_s']:.2f} "
              f"t_comp={rec['t_compute_s']:.2f} dom={rec['dominant']}")
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-3000:])
        print(f"[{name}] FAIL {rec['error'][:200]}")
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = "multipod" if multi_pod else "singlepod"
    (out_dir / f"{arch}__{shape}__{tag}__{name}.json").write_text(
        json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--variant", default=None)
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()
    parts = args.cell.split(":")
    arch, shape = parts[0], parts[1]
    multi = len(parts) > 2 and parts[2] == "mp"
    variants = VARIANTS[args.cell]
    if args.variant:
        variants = {args.variant: variants[args.variant]}
    for name, (mo, bo, so) in variants.items():
        run_variant(arch, shape, multi, mo, bo, so, name, Path(args.out))


if __name__ == "__main__":
    main()
