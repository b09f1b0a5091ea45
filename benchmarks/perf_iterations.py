"""SPerf hillclimb measurements: re-lower the three selected cells with
the optimization variants and print before/after roofline terms.

A. qwen2-moe train_4k  — expert padding 60->64 => EP shards the 16-way
   model axis (baseline: replicated expert compute).
B. llava-next train_4k — q-head padding 56->64 => head-sharded attention
   (baseline: replicated-attention fallback).
C. gemma2-2b long_500k — sliding-window cache slice on decode for the 13
   local layers (baseline: every layer streams the full 524k cache).
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402

import repro.configs as cfgs                      # noqa: E402
from repro.launch import dryrun as dr             # noqa: E402


def measure(arch, shape, override=None, window_cache=False, tag=""):
    orig = cfgs.get_config
    if override:
        cfg0 = orig(arch)
        patched = dataclasses.replace(cfg0, **override)
        cfgs.get_config = lambda a: patched if a == arch else orig(a)
        dr.get_config = cfgs.get_config
    try:
        cfg, sh, mesh, lowered, extra = dr.lower_cell(arch, shape, False)
        if window_cache:
            extra["window_cache"] = True
        rec = dr.analyze(cfg, sh, mesh, lowered, extra)
    finally:
        cfgs.get_config = orig
        dr.get_config = orig
    ro = rec["roofline"]
    print(f"[{tag}] {arch} x {shape}: dominant={ro['dominant']} "
          f"compute={ro['compute_s']:.4f} memory={ro['memory_s']:.4f} "
          f"collective={ro['collective_s']:.4f} mfu_bound={ro['mfu_bound']:.3f}")
    os.makedirs("results/perf", exist_ok=True)
    with open(f"results/perf/{arch}__{shape}__{tag}.json", "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="one optimization pair (A baseline vs padded EP) "
                         "instead of the full three-cell sweep")
    ap.add_argument("--json", action="store_true",
                    help="print a final machine-readable summary line "
                         "(for benchmarks/run.py)")
    args = ap.parse_args(argv)

    recs = {}
    # A: expert padding
    recs["A_baseline"] = measure("qwen2-moe-a2.7b", "train_4k",
                                 tag="A_baseline")
    recs["A_padded_ep"] = measure("qwen2-moe-a2.7b", "train_4k",
                                  override={"n_experts_pad": 64},
                                  tag="A_padded_ep")
    if not args.quick:
        # B: head padding
        recs["B_baseline"] = measure("llava-next-34b", "train_4k",
                                     tag="B_baseline")
        recs["B_padded_heads"] = measure("llava-next-34b", "train_4k",
                                         override={"n_heads_pad": 64},
                                         tag="B_padded_heads")
        # C: window cache (code change is live; compare against the
        # analytic full-cache memory term recorded by the v2 sweep
        # baseline)
        recs["C_window_cache"] = measure("gemma2-2b", "long_500k",
                                         window_cache=True,
                                         tag="C_window_cache")
        recs["C_window_cache_32k"] = measure("gemma2-2b", "decode_32k",
                                             window_cache=True,
                                             tag="C_window_cache_32k")
    if args.json:
        summary = {
            "schema": "dial-perf-iterations-v1",
            "quick": args.quick,
            "measures": {tag: {k: rec["roofline"][k]
                               for k in ("dominant", "compute_s",
                                         "memory_s", "collective_s",
                                         "mfu_bound")}
                         for tag, rec in recs.items()},
        }
        print(json.dumps(summary))
    return recs


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
