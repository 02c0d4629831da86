"""enum.dispatches_per_query: frontier kernel dispatches
(``ops.device_dispatch_count``) in the window per distinct query."""


def read(rec):
    """Dispatches per distinct query."""
    distinct = sum(b["distinct"] for b in rec["batches"])
    if not distinct:
        return None
    return rec["dispatches"] / distinct
