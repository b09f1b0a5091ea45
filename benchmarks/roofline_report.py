"""Render the EXPERIMENTS.md roofline table from results/dryrun/*.json."""

from __future__ import annotations

import glob
import json
import os


def load(out_dir: str = "results/dryrun") -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def table(recs: list[dict], mesh: str = "pod") -> str:
    rows = ["| arch | shape | dominant | compute s | memory s | collective s "
            "| MODEL_FLOPs/HLO | MFU bound |",
            "|---|---|---|---|---|---|---|---|"]
    want = 2 if mesh == "pod" else 3
    for r in recs:
        if len(r["mesh"]) != want:
            continue
        ro = r["roofline"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | **{ro['dominant']}** "
            f"| {ro['compute_s']:.4f} | {ro['memory_s']:.4f} "
            f"| {ro['collective_s']:.4f} | {ro['useful_flops_frac']:.2f} "
            f"| {ro['mfu_bound']:.3f} |")
    return "\n".join(rows)


def main():
    import sys
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "results/dryrun_v2"
    recs = load(out_dir)
    print(f"{len(recs)} dry-run records")
    print("\n## single-pod (16x16 = 256 chips)\n")
    print(table(recs, "pod"))
    print("\n## multi-pod (2x16x16 = 512 chips)\n")
    print(table(recs, "multipod"))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
