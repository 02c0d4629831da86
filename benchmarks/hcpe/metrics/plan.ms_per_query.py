"""plan.ms_per_query: planner milliseconds (``optimize_seconds``) per
distinct query of the window's micro-batches."""


def read(rec):
    """Planner milliseconds per distinct query."""
    distinct = sum(b["distinct"] for b in rec["batches"])
    if not distinct:
        return None
    return sum(b["optimize_s"] for b in rec["batches"]) * 1e3 / distinct
