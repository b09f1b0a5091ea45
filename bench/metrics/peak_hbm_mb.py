"""Peak device memory in use after the window, in MB
(``memory_stats()["peak_bytes_in_use"]``)."""


def read(rec):
    return rec["peak_bytes"] / 1e6 if rec["peak_bytes"] else None
