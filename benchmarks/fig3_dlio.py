"""Paper Fig. 3: deep-learning I/O kernels (DLIO) — DIAL vs default.

BERT- and Megatron-style readers across OST utilization x thread counts.
The paper reports up to 1.75x over the default configuration.
"""

from __future__ import annotations

from repro.core.agent import run_with_agents
from repro.core.model import DIALModel
from repro.pfs import PFSSim
from repro.pfs.workloads import dlio_reader

SECONDS = 25.0

CASES = [
    # (model, n_threads, n_osts_used)
    ("bert", 2, 1), ("bert", 8, 1), ("bert", 16, 2), ("bert", 32, 4),
    ("megatron", 2, 1), ("megatron", 8, 1), ("megatron", 16, 2),
    ("megatron", 32, 4),
]


def _run(model_name, threads, osts, dial_model=None, seed=13):
    sim = PFSSim(n_clients=1, n_osts=8, seed=seed)
    wl = dlio_reader(0, model_name, threads, osts=tuple(range(osts)))
    sim.attach(wl)
    # Lustre defaults
    sim.set_knobs(sim.client_oscs(0), window_pages=256, rpcs_in_flight=8)
    if dial_model is not None:
        run_with_agents(sim, dial_model, [0], SECONDS)
    else:
        sim.run(SECONDS)
    return wl.done_bytes(sim) / SECONDS / 1e6


def run(model_path: str = "models/dial") -> list[dict]:
    model = DIALModel.load(model_path)
    rows = []
    for m, t, o in CASES:
        base = _run(m, t, o)
        dial = _run(m, t, o, dial_model=model)
        rows.append({"kernel": m, "threads": t, "osts": o,
                     "default_mbs": round(base, 1),
                     "dial_mbs": round(dial, 1),
                     "speedup": round(dial / max(base, 1e-9), 2)})
    return rows


def main():
    rows = run()
    for r in rows:
        print(f"DLIO-{r['kernel']:9s} t={r['threads']:2d} osts={r['osts']}: "
              f"default={r['default_mbs']:7.1f}  DIAL={r['dial_mbs']:7.1f}  "
              f"({r['speedup']:.2f}x)")
    best = max(r["speedup"] for r in rows)
    print(f"max speedup over default: {best:.2f}x (paper: up to 1.75x)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
