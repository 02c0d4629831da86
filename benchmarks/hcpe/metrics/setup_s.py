"""setup_s: seconds from the start of the process to the start of the
window: JAX start-up, graph generation and load, server start, warm-up
and every compile it makes."""


def read(rec):
    """The set-up time the run measured on the host clock."""
    return rec["setup_s"]
