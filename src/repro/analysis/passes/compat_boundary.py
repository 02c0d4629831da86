"""compat-boundary: jax mesh/sharding APIs only in compat.py (DESIGN.md §6, §11).

The repo spells the jax mesh and sharding entry points it uses
(``shard_map``, ``make_mesh``, ``AxisType``, ``set_mesh``,
``get_abstract_mesh``) once, in ``src/repro/compat.py``.  Any call site
that spells one of them directly — or a retired spelling such as
``jax.experimental.shard_map`` or the ``check_rep``/``check_vma``
kwargs — is a finding.  ``tests/test_compat.py`` is a thin wrapper over
this pass.

``compat.py`` itself is exempt (it *is* the boundary), as is
``tests/test_compat.py`` (it asserts against the jax spellings).
"""
from __future__ import annotations

import re
from typing import Iterator

from ..framework import Finding, LintPass, SourceFile

# This module necessarily spells the forbidden symbols (docstring and
# pattern source), so it suppresses itself — the mechanism the rest of
# the repo uses for intentional one-off exemptions.
# repro-lint: disable-file=compat-boundary

SKEW_PATTERN = re.compile(
    # modern-only spellings
    r"jax\.set_mesh|jax\.shard_map|jax\.make_mesh"
    r"|jax\.sharding\.AxisType|jax\.sharding\.get_abstract_mesh"
    r"|jax\.sharding\.use_mesh"
    # retired spellings
    r"|jax\.experimental\.shard_map"
    r"|check_vma|check_rep")

# the boundary itself and the test that pins both of its sides
EXEMPT_BASENAMES = ("compat.py", "test_compat.py")


class CompatBoundaryPass(LintPass):
    """Line scan for mesh/sharding jax APIs outside the compat layer."""

    name = "compat-boundary"
    description = ("jax mesh/sharding symbols (shard_map/make_mesh/"
                   "AxisType/check_rep/...) appear only in "
                   "src/repro/compat.py (DESIGN.md §6)")
    scope = ("src/*.py", "src/**/*.py", "tests/*.py", "tests/**/*.py",
             "benchmarks/*.py", "benchmarks/**/*.py",
             "examples/*.py", "examples/**/*.py")

    def applies_to(self, rel: str) -> bool:
        if rel.rsplit("/", 1)[-1] in EXEMPT_BASENAMES:
            return False
        return super().applies_to(rel)

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        for ln, line in enumerate(sf.lines, 1):
            m = SKEW_PATTERN.search(line)
            if m:
                yield self.finding(sf, ln, (
                    f"jax API {m.group(0)!r} outside repro/compat.py "
                    f"— route it through the compat layer (DESIGN.md §6)"))


PASSES = [CompatBoundaryPass()]
