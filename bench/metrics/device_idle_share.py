"""1 - the union of device-op intervals over the traced window: the
window's first whole job or, where the trace dropped events, the part of
it up to the last op kept."""


def read(rec):
    return None if rec["trace"] is None else rec["trace"]["idle_share"]
