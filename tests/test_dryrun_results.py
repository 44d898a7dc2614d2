"""Integrity checks over the committed dry-run artifact (the multi-pod
deliverable): every required cell present, compiled, and within HBM."""

import json
import os

import pytest

RESULTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "dryrun_results.json")

pytestmark = pytest.mark.skipif(
    not os.path.exists(RESULTS),
    reason="dryrun_results.json not generated yet "
           "(python -m repro.launch.dryrun --all --both-meshes)",
)


def _load():
    with open(RESULTS) as f:
        return json.load(f)


def test_all_required_cells_present_and_clean():
    from repro.configs.base import list_archs, shape_cells

    d = _load()
    missing, errors = [], []
    for mesh in ["16x16", "2x16x16"]:
        for arch in list_archs():
            for sh in shape_cells(arch):
                key = f"{arch}|{sh}|{mesh}"
                if key not in d:
                    missing.append(key)
                elif "error" in d[key]:
                    errors.append(key)
    assert not missing, missing
    assert not errors, errors


def test_every_cell_fits_hbm():
    d = _load()
    over = [
        k for k, v in d.items()
        if "error" not in v and "bytes_per_device" in v
        and v["bytes_per_device"]["peak"] > 16 * 2**30
    ]
    assert not over, over


def test_roofline_terms_positive_and_consistent():
    d = _load()
    for k, v in d.items():
        if "error" in v or "roofline" not in v:
            continue
        r = v["roofline"]
        assert r["compute_s"] >= 0 and r["memory_s"] >= 0
        assert r["collective_s"] >= 0
        assert r["bound_s"] == pytest.approx(
            max(r["compute_s"], r["memory_s"], r["collective_s"]), rel=1e-6)
        assert r["dominant"].replace("_s", "") in ("compute", "memory", "collective")


def test_multipod_pod_axis_engaged():
    """The 2x16x16 cells must actually spread over 512 devices."""
    d = _load()
    mp = [v for k, v in d.items()
          if v.get("mesh") == "2x16x16" and "error" not in v]
    assert mp and all(v["devices"] == 512 for v in mp)


def test_optimized_variants_beat_baseline():
    """§Perf: the persisted fsdp variants must have a lower collective term
    than their tp_sp baselines (the confirmed H1 hypothesis)."""
    d = _load()
    for arch in ["mamba2-370m", "yi-6b", "deepseek-moe-16b"]:
        base = d.get(f"{arch}|train_4k|16x16")
        opt = d.get(f"{arch}|train_4k|16x16|fsdp")
        if base is None or opt is None:
            pytest.skip("optimized variants not generated")
        assert opt["roofline"]["collective_s"] < base["roofline"]["collective_s"]
        assert opt["roofline"]["bound_s"] < base["roofline"]["bound_s"]
