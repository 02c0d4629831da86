"""qps: answers completed ``ok`` in the window per second of it (the
window runs from its start to the last answer of a request sent in it,
so every request sent counts, and all the time they took)."""


def read(rec):
    """Completed ok answers over the window's seconds."""
    ok = sum(1 for r in rec["requests"] if r["ok"])
    return ok / rec["window_s"] if ok else None
