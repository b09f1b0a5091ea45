"""Seconds of set-up that the program's own steps spent making programs:
tracing, lowering to MLIR and backend compile (whose time holds each
persistent-cache retrieval), from the program's compile table
(``repro.obs.spans.totals()``), over the rows of its ``dial.*`` spans.

The table is read after the window.  A sound window compiles nothing,
so the rows then hold set-up alone; where the window compiled
(``compiles_in_window``) nothing is reported.  Programs made outside the
program's spans (the benchmark's own, and the HLO texts a traced run
reads back) fall under ``"none"`` and are left out.  Nothing where the
program has no compile table."""


def read(rec):
    if rec["compiles_in_window"]:
        return None
    try:
        from repro.obs import spans
    except ImportError:
        return None
    rows = [row for name, row in spans.totals()["compiles"].items()
            if name.startswith("dial.")]
    if not rows:
        return None
    return sum(row["trace_s"] + row["lower_s"] + row["backend_s"]
               for row in rows)
