"""Device busy milliseconds per tuning interval: the busy time of the
window's first whole job (every dispatch it made) over the intervals it
simulated.  Nothing where the trace dropped events: a job traced in
part has no whole number of intervals."""


def read(rec):
    t = rec["trace"]
    if t is None or not t["complete"]:
        return None
    return t["busy_s"] * 1e3 / rec["traced_intervals"]
