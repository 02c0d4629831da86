"""The HcPE serving benchmark (``run.py``) and its yardstick: graph and
traffic generation, the plain reference, the trace reduction and one
reader per metric."""
