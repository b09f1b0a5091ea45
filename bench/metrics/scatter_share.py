"""Share of device busy time in ops whose XLA name is a scatter: the
segment sums of the engine as XLA compiles them today (float64 pairs
reduced by scatter-add).  Tied to that lowering; named scopes will
replace it."""


def read(rec):
    return None if rec["trace"] is None else rec["trace"]["scatter_share"]
