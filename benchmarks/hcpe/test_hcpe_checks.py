"""The correctness check fails what it must: the control (walks in place
of simple paths) and the program with its answers altered where they are
produced both come out not ``correct`` (CPU, tiny size)."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from hcpe import control, run

MIXES = ["k4_hot", "k4_solo"]
SEED = 2**31 + 9


def failing(out: dict) -> dict:
    return {k: v["value"] for k, v in out["checks"].items()
            if v["value"] > v["limit"]}


@pytest.mark.parametrize("mix", MIXES)
def test_control_is_not_correct(mix, tiny_root, no_compile_cache):
    out = control.control_run(f"tiny.{mix}", SEED, 0.2,
                              root=tiny_root, require_tpu=False,
                              log=lambda m: None)
    assert not out["correct"]
    assert failing(out) and "unanswered" not in failing(out)


def altered(res):
    """One answer altered where the driver produced it: a count off by
    one, or t written into the interior of a returned path."""
    if res.paths.shape[0] == 0:
        return dataclasses.replace(res, count=res.count + 1)
    paths = res.paths.copy()
    row = int(np.argmax(res.lengths >= 2)) if (res.lengths >= 2).any() else 0
    paths[row, 1] = paths[row, res.lengths[row]]
    return dataclasses.replace(res, paths=paths)


@pytest.mark.parametrize("mix", MIXES)
def test_altered_answers_are_not_correct(mix, tiny_root, monkeypatch,
                                         no_compile_cache):
    from repro.core import batch, fused
    fused_run = fused.enumerate_fused_device
    solo_run = batch.enumerate_paths_idx
    monkeypatch.setattr(fused, "enumerate_fused_device",
                        lambda *a, **kw: [altered(r)
                                          for r in fused_run(*a, **kw)])
    monkeypatch.setattr(batch, "enumerate_paths_idx",
                        lambda *a, **kw: altered(solo_run(*a, **kw)))
    out = run.run_cell(f"tiny.{mix}", SEED, 2.0, False, root=tiny_root,
                       require_tpu=False, log=lambda m: None)
    assert not out["correct"]
    assert failing(out)
