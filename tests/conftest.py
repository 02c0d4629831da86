import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def hub_graph():
    """Factory for a hub graph whose index fan-out is chosen: s=0 → hubs
    (2, 3, …) → leaves → t=1, plus s → z with z a dead end (the last
    vertex).  Hub 0 has exactly ``fanout`` leaves, the others a random
    half-to-full subset; even hubs also reach t directly, and every hub
    links back to s (a prefix duplicate at the end of its index list once
    the budget reaches 2)."""
    from repro.core import from_edges

    def make(hubs, fanout, seed):
        rng = np.random.default_rng(seed)
        leaves = np.arange(2 + hubs, 2 + hubs + fanout + 64)
        z = int(leaves[-1]) + 1
        edges = [(0, z)] + [(int(v), 1) for v in leaves]
        for i in range(hubs):
            h = 2 + i
            deg = fanout if i == 0 else int(rng.integers(fanout // 2, fanout))
            edges += [(0, h), (h, 0)] + [(h, int(v)) for v in
                                         rng.choice(leaves, deg, replace=False)]
            if i % 2 == 0:
                edges.append((h, 1))
        return from_edges(z + 1, np.array(edges))
    return make
