"""Host seconds per job outside the program's device calls (the traffic's
``device_calls``, ``FusedLoop.run``, which dispatches and waits): the
lab's work around and inside its entry (jittering, building, bucketing
and stacking scenarios, disturbance schedules, decoding decisions,
merging results), on the host's clock, over every job of the window."""


def read(rec):
    host = [j.host_s for j in rec["jobs"] if j.host_s is not None]
    return sum(host) / len(host) if host else None
