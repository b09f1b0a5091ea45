"""Share of the dispatched interfaces that are phantom padding, over
every batch the window dispatched (``ScenarioBatch.pad_stats``)."""


def read(rec):
    stats = [b for j in rec["jobs"] for b in j.buckets]
    total = sum(b["total_interfaces"] for b in stats)
    return sum(b["phantom_interfaces"] for b in stats) / total
