"""Paper Table II: H5bench-style scientific workloads — DIAL vs optimal.

VPIC-IO (1/2/3-D contiguous array writes) and BDCATS-IO
(partial/strided/full reads).  'Optimal' is an exhaustive grid search over
the configuration space per workload (what the paper measured offline);
DIAL starts from Lustre defaults and tunes online.  The paper's claim:
DIAL lands within a few percent of optimal.
"""

from __future__ import annotations

from repro.core.agent import run_with_agents
from repro.core.config_space import SPACE
from repro.core.model import DIALModel
from repro.pfs import PFSSim
from repro.pfs.workloads import bdcats_read, vpic_write

SECONDS = 20.0


def _run(make_wl, window, inflight, tuned_model=None, seconds=SECONDS,
         seed=11):
    sim = PFSSim(n_clients=1, n_osts=8, seed=seed)
    wl = make_wl()
    sim.attach(wl)
    sim.set_knobs(sim.client_oscs(0), window_pages=window,
                  rpcs_in_flight=inflight)
    if tuned_model is not None:
        run_with_agents(sim, tuned_model, [0], seconds)
    else:
        sim.run(seconds)
    return wl.done_bytes(sim) / seconds / 1e6


def optimal(make_wl) -> tuple[float, tuple]:
    best, best_cfg = -1.0, None
    for w, f in SPACE.configs():
        t = _run(make_wl, w, f)
        if t > best:
            best, best_cfg = t, (w, f)
    return best, best_cfg


WORKLOADS = [
    ("VPIC-IO (1D array write)", lambda: vpic_write(0, 1)),
    ("VPIC-IO (2D array write)", lambda: vpic_write(0, 2)),
    ("VPIC-IO (3D array write)", lambda: vpic_write(0, 3)),
    ("BDCATS-IO (partial read)", lambda: bdcats_read(0, "partial")),
    ("BDCATS-IO (strided read)", lambda: bdcats_read(0, "strided")),
    ("BDCATS-IO (full read)", lambda: bdcats_read(0, "full")),
]


def run(model_path: str = "models/dial") -> list[dict]:
    model = DIALModel.load(model_path)
    rows = []
    for name, mk in WORKLOADS:
        opt, opt_cfg = optimal(mk)
        dial = _run(mk, 256, 8, tuned_model=model)   # from Lustre defaults
        rows.append({"workload": name, "optimal_mbs": round(opt, 1),
                     "optimal_cfg": opt_cfg, "dial_mbs": round(dial, 1),
                     "dial_frac_of_optimal": round(dial / opt, 3)})
    return rows


def main():
    for r in run():
        print(f"{r['workload']:28s} optimal={r['optimal_mbs']:8.1f} MB/s "
              f"(w={r['optimal_cfg'][0]},f={r['optimal_cfg'][1]})  "
              f"DIAL={r['dial_mbs']:8.1f} MB/s "
              f"({100 * r['dial_frac_of_optimal']:.1f}% of optimal)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
