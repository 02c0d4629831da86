"""Mesh-sharded PathEnum: engine.py (DESIGN.md §2, last bullet)."""
