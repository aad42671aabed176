"""RPL601 noncanonical-import: kernel-family shared helpers must be
imported from ``repro.kernels.common``, their canonical home.

``auto_interpret`` / ``pad_leading`` are re-exported by the families'
``kernel.py`` for historical reasons; importing them *through* a family
module couples unrelated families (condat ops depending on condat
kernel internals for a backend-selection helper) and means an import
like ``from repro.kernels.X.kernel import auto_interpret`` silently
pins behavior to whichever module re-exported it.  One canonical home
keeps env-override behavior (``REPRO_FORCE_INTERPRET``) in one place.
"""
from __future__ import annotations

import ast
from typing import List

from repro.lint.core import Finding, ModuleSource, Rule, register_checker

RPL601 = Rule("RPL601", "noncanonical-import",
              "shared kernel helper imported from a non-canonical module")

_CANONICAL = "repro.kernels.common"
_SHARED_HELPERS = {"auto_interpret", "pad_leading"}


@register_checker("imports", [RPL601])
def check(mod: ModuleSource):
    findings: List[Finding] = []
    # common.py itself defines the helpers; kernel.py re-exports are
    # tolerated for backwards compatibility but must come from common
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ImportFrom) or node.module is None:
            continue
        if node.module == _CANONICAL or node.level > 0:
            continue
        if not node.module.startswith("repro.kernels."):
            continue
        for alias in node.names:
            if alias.name in _SHARED_HELPERS:
                findings.append(mod.finding(
                    RPL601, node,
                    f"'{alias.name}' imported from '{node.module}' — "
                    f"import it from its canonical home "
                    f"'{_CANONICAL}' instead"))
    return findings
