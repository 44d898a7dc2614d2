import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, WITHOUT allocating any real model:
  * compiled.memory_analysis()   -> bytes per device (fits-in-HBM proof)
  * compiled.cost_analysis()     -> HLO flops / bytes     (roofline terms)
  * collective bytes parsed from the compiled HLO text    (roofline term 3)

Results are cached incrementally in dryrun_results.json so interrupted runs
resume. Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only-cell ...]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs.base import SHAPES, get_config, list_archs, shape_cells
from repro.dist import steps as steps_lib
from repro.dist.sharding import ShardingPolicy
from repro.launch.hlo import collective_bytes_from_hlo  # noqa: F401 (re-export)
from repro.launch.mesh import TPU_V5E, make_production_mesh

RESULTS_PATH = os.path.abspath(os.environ.get(
    "DRYRUN_RESULTS",
    os.path.join(os.path.dirname(__file__), "..", "..", "..",
                 "dryrun_results.json")))

def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) for train; 2*N*D for inference."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


# Per-cell gradient-accumulation overrides: archs whose attention heads do
# not divide the TP degree (hymba: 25) can't shard attention interiors; the
# standard production lever is microbatching the global batch.
MICROBATCH_OVERRIDES = {
    ("hymba-1.5b", "train_4k"): 2,
}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             policy=None, remat: bool = True, quiet: bool = False,
             microbatches: int = 0, strategy: str = "tp_sp") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    if not microbatches:
        microbatches = MICROBATCH_OVERRIDES.get((arch, shape_name), 1)
    if policy is None and strategy != "tp_sp":
        policy = ShardingPolicy(strategy=strategy)
    t0 = time.time()
    cell = steps_lib.build_cell(cfg, shape, mesh, policy=policy, remat=remat,
                                microbatches=microbatches)
    lowered = cell.lower(mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)

    flops_total = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # memory_analysis is per-device on SPMD executables
        "bytes_per_device": {
            "argument": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak": int(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)
            ),
        },
        "hlo_flops_per_device": flops_total,
        "hlo_bytes_per_device": bytes_accessed,
        "collectives": coll,
        "model_flops_global": model_flops(cfg, shape),
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }
    # roofline terms — two sources:
    #  * hlo_*: from the compiled artifact. CAVEAT: HloCostAnalysis visits
    #    while-loop bodies ONCE, so scan-over-layers flops/bytes are
    #    under-counted ~L-fold. Kept as the compiled cross-check (and the
    #    collective schedule is real).
    #  * analytic: repro.dist.costs — exact matmul accounting per cell;
    #    these are the §Roofline numbers.
    peak = TPU_V5E["peak_flops_bf16"]
    hbm = TPU_V5E["hbm_bandwidth"]
    ici = TPU_V5E["ici_link_bandwidth"]
    out["roofline_hlo"] = {
        "compute_s": flops_total / peak,
        "memory_s": bytes_accessed / hbm,
        "collective_s": coll["total_bytes"] / ici,
    }
    from repro.dist.costs import cell_costs

    costs = cell_costs(cfg, shape, dict(mesh.shape), strategy=strategy)
    rf = costs.roofline()
    out["roofline"] = {
        "compute_s": rf["compute_s"],
        "memory_s": rf["memory_s"],
        "collective_s": rf["collective_s"],
        "dominant": rf["dominant"],
        "bound_s": rf["bound_s"],
        "mfu_bound": rf["mfu_bound"],
        "useful_flops_ratio": costs.model_flops_global
        / max(costs.flops * n_dev, 1.0),
    }
    out["analytic"] = {
        "flops_per_device": costs.flops,
        "hbm_bytes_per_device": costs.hbm_bytes,
        "collective_bytes_per_device": costs.collective_bytes,
    }
    if not quiet:
        hbm_ok = out["bytes_per_device"]["peak"] <= TPU_V5E["hbm_bytes"]
        r = out["roofline"]
        print(
            f"[dryrun] {arch} x {shape_name} x {out['mesh']}: "
            f"compile {t_compile:.0f}s, peak/dev "
            f"{out['bytes_per_device']['peak']/2**30:.2f} GiB "
            f"({'fits' if hbm_ok else 'OVER'}), dominant={r['dominant']}, "
            f"terms c/m/n = {r['compute_s']*1e3:.2f}/"
            f"{r['memory_s']*1e3:.2f}/"
            f"{r['collective_s']*1e3:.2f} ms, mfu_bound={r['mfu_bound']:.3f}"
        )
    return out


def load_results() -> dict:
    if os.path.exists(RESULTS_PATH):
        with open(RESULTS_PATH) as f:
            return json.load(f)
    return {}


def save_result(key: str, value: dict) -> None:
    results = load_results()
    results[key] = value
    tmp = RESULTS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, RESULTS_PATH)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (512-chip) mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--strategy", default="tp_sp", choices=["tp_sp", "fsdp"],
                    help="sharding strategy (fsdp = the §Perf-winning ZeRO-3)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    assert jax.device_count() == 512, (
        "dry-run needs the 512 placeholder devices; do not import jax before "
        "this module sets XLA_FLAGS"
    )

    cells: list[tuple[str, str, bool]] = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    for arch in archs:
        shapes = shape_cells(arch) if (args.all or not args.shape) else [args.shape]
        for sh in shapes:
            if args.both_meshes:
                cells.append((arch, sh, False))
                cells.append((arch, sh, True))
            else:
                cells.append((arch, sh, args.multi_pod))

    failures = 0
    for arch, sh, mp in cells:
        key = f"{arch}|{sh}|{'2x16x16' if mp else '16x16'}"
        if not args.force and key in load_results():
            print(f"[dryrun] cached: {key}")
            continue
        try:
            res = run_cell(arch, sh, multi_pod=mp)
            save_result(key, res)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"[dryrun] FAIL {key}: {type(e).__name__}: {e}")
            traceback.print_exc()
            save_result(key, {"error": f"{type(e).__name__}: {e}"[:500],
                              "arch": arch, "shape": sh})
    print(f"[dryrun] done, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
