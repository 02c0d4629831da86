"""Backend compiles (``jax.monitoring`` compile events, a persistent-cache
read included) inside the window: shape buckets that warm-up missed."""


def read(rec):
    """Compiles in the window."""
    return rec["compiles"]["count"]
