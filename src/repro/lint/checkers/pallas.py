"""Pallas kernel contracts.

RPL201 blockspec-grid      : BlockSpec block shape / index map is
                             inconsistent with the grid expression.
RPL202 missing-interpret   : a ``pl.pallas_call`` site without the
                             ``interpret=`` fallback plumbing.
RPL203 ref-parity          : a kernel family's ``ref.py`` oracle and
                             ``ops.py`` public wrapper disagree on
                             signatures (checked by import-and-inspect,
                             not string matching), or a sibling is
                             missing entirely.

The grid/BlockSpec check leans on this codebase's kernel idiom: 1-D (or
n-D) grids of the form ``grid=(padded // block, ...)`` or
``grid=(pl.cdiv(rows, block), ...)`` (a ragged last block) with
``pl.BlockSpec((block, ...), lambda i, ...: (i, 0, 0))``.  For each grid
axis it finds the position where the lambda parameter appears in the
index map's return tuple and requires the block shape at that position
to be the same name as the grid divisor.  Specs built by helper calls
(e.g. SMEM scalar specs) are skipped — only literal ``pl.BlockSpec``
calls are validated.
"""
from __future__ import annotations

import ast
import importlib
import importlib.util
import inspect
from pathlib import Path
from typing import Dict, List, Optional

from repro.lint.checkers._ast_util import (dotted, functions,
                                           import_aliases, param_names,
                                           resolve)
from repro.lint.core import Finding, ModuleSource, Rule, register_checker

RPL201 = Rule("RPL201", "blockspec-grid",
              "BlockSpec block shape inconsistent with pallas_call grid")
RPL202 = Rule("RPL202", "missing-interpret",
              "pallas_call without an interpret fallback path")
RPL203 = Rule("RPL203", "ref-parity",
              "kernel ops.py / ref.py signature parity violation")

# kernel-control parameters the public wrapper may add on top of the
# oracle's mathematical signature
_CONTROL_PARAMS = {"use_kernel", "interpret"}


def _is_pallas_call(call: ast.Call, aliases) -> bool:
    name = resolve(call.func, aliases)
    return name is not None and name.split(".")[-1] == "pallas_call" \
        and ("pallas" in name or name.startswith("pl."))


def _kw(call: ast.Call, name: str):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _grid_axes(grid_node) -> Optional[List]:
    """Grid expression -> list of per-axis AST nodes (None = opaque)."""
    if grid_node is None:
        return None
    if isinstance(grid_node, ast.Tuple):
        return list(grid_node.elts)
    return [grid_node]       # grid=8 / grid=n_full // block


def _block_divisor(axis_node) -> Optional[str]:
    """``padded // block`` or ``pl.cdiv(rows, block)`` -> "block" (the
    name the block shape must use); None when the axis expression has
    another shape."""
    if isinstance(axis_node, ast.BinOp) and \
            isinstance(axis_node.op, ast.FloorDiv):
        return dotted(axis_node.right)
    if isinstance(axis_node, ast.Call) and len(axis_node.args) == 2 and \
            (dotted(axis_node.func) or "").split(".")[-1] == "cdiv":
        return dotted(axis_node.args[1])
    return None


def _blockspecs(call: ast.Call, aliases):
    """Literal ``pl.BlockSpec(...)`` calls in in_specs/out_specs, as
    ``(spec, is_output)`` pairs."""
    specs = []
    for kw_name in ("in_specs", "out_specs"):
        node = _kw(call, kw_name)
        if node is None:
            continue
        entries = node.elts if isinstance(node, (ast.List, ast.Tuple)) \
            else [node]
        for e in entries:
            if isinstance(e, ast.Call):
                name = resolve(e.func, aliases)
                if name and name.split(".")[-1] == "BlockSpec":
                    specs.append((e, kw_name == "out_specs"))
    return specs


def _index_map_positions(lam: ast.Lambda) -> Optional[Dict[str, int]]:
    """lambda i, j: (j, i, 0) -> {"i": 1, "j": 0}; None if opaque."""
    body = lam.body
    elts = body.elts if isinstance(body, ast.Tuple) else [body]
    out: Dict[str, int] = {}
    for pos, el in enumerate(elts):
        if isinstance(el, ast.Name):
            if el.id in out:
                return None
            out[el.id] = pos
    return out


def _check_grid_site(mod, call, aliases, findings) -> None:
    grid_axes = _grid_axes(_kw(call, "grid"))
    for spec, is_output in _blockspecs(call, aliases):
        if spec.keywords and not spec.args:
            continue                       # memory_space-only (SMEM) spec
        if not spec.args:
            continue
        shape_node = spec.args[0]
        lam = spec.args[1] if len(spec.args) > 1 else None
        if not isinstance(shape_node, ast.Tuple):
            continue
        block_dims = shape_node.elts
        if lam is None or not isinstance(lam, ast.Lambda):
            continue
        lam_params = [a.arg for a in lam.args.args]
        if grid_axes is not None and len(lam_params) != len(grid_axes):
            findings.append(mod.finding(
                RPL201, spec,
                f"index map takes {len(lam_params)} grid indices but the "
                f"grid has {len(grid_axes)} axes"))
            continue
        positions = _index_map_positions(lam)
        if positions is None:
            continue
        # every grid index must steer some block dimension of an
        # *input* spec; outputs may pin a block across grid steps (the
        # sequential-grid accumulator idiom, e.g. dict_outer)
        if not is_output:
            for p in lam_params:
                if p not in positions:
                    findings.append(mod.finding(
                        RPL201, spec,
                        f"grid index '{p}' never appears in the index "
                        f"map return — a whole grid axis reads the "
                        f"same input block"))
        if grid_axes is None:
            continue
        for axis_i, p in enumerate(lam_params):
            pos = positions.get(p)
            if pos is None:
                continue
            if pos >= len(block_dims):
                findings.append(mod.finding(
                    RPL201, spec,
                    f"index map position {pos} exceeds the "
                    f"{len(block_dims)}-d block shape"))
                continue
            divisor = _block_divisor(grid_axes[axis_i])
            block_name = dotted(block_dims[pos])
            if divisor is not None and block_name is not None \
                    and divisor != block_name:
                findings.append(mod.finding(
                    RPL201, spec,
                    f"grid axis {axis_i} steps in units of '{divisor}' "
                    f"but the block shape at position {pos} is "
                    f"'{block_name}' — block/grid math disagrees"))


def _check_interpret(mod, call, aliases, owner_fn, findings) -> None:
    if _kw(call, "interpret") is None:
        findings.append(mod.finding(
            RPL202, call,
            "pallas_call without interpret= — non-TPU backends have no "
            "fallback path (pass interpret=interpret resolved via "
            "repro.kernels.common.auto_interpret)"))
        return
    if owner_fn is not None and "interpret" not in param_names(owner_fn):
        findings.append(mod.finding(
            RPL202, call,
            f"'{owner_fn.name}' hardcodes the pallas_call interpret "
            f"mode — accept an interpret=None parameter and resolve it "
            f"via repro.kernels.common.auto_interpret"))


# --------------------------------------------------------------------
# ref.py <-> ops.py parity (import-and-inspect)
# --------------------------------------------------------------------

def _module_name_for(path: Path) -> Optional[str]:
    """Importable dotted module name for a file inside the repro
    package (resolved through its __init__.py chain), else None."""
    parts = list(path.with_suffix("").parts)
    if "repro" not in parts:
        return None
    idx = len(parts) - 1 - parts[::-1].index("repro")
    return ".".join(parts[idx:])


def _load_module(path: Path):
    name = _module_name_for(path)
    if name is not None:
        return importlib.import_module(name)
    # fixture files outside the package: load standalone by location
    spec = importlib.util.spec_from_file_location(
        f"_repro_lint_fixture_{abs(hash(str(path)))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _math_params(fn) -> List[str]:
    """Signature minus the kernel-control knobs (use_kernel/interpret/
    block_*) — the part that must agree between oracle and wrapper."""
    out = []
    for name, p in inspect.signature(fn).parameters.items():
        if name in _CONTROL_PARAMS or name.startswith("block_"):
            continue
        if p.kind in (inspect.Parameter.VAR_POSITIONAL,
                      inspect.Parameter.VAR_KEYWORD):
            continue
        out.append(name)
    return out


def _check_parity(mod: ModuleSource) -> List[Finding]:
    findings: List[Finding] = []
    ops_path = mod.path
    ref_path = ops_path.with_name("ref.py")
    if not ref_path.exists():
        findings.append(Finding(
            str(ops_path), 1, 0, RPL203,
            "kernel family has no sibling ref.py oracle"))
        return findings
    try:
        ops_mod = _load_module(ops_path)
        ref_mod = _load_module(ref_path)
    except Exception as e:                  # pragma: no cover - env issue
        findings.append(Finding(
            str(ops_path), 1, 0, RPL203,
            f"could not import ops/ref pair for parity check: {e!r}"))
        return findings
    for ref_name in dir(ref_mod):
        if ref_name.startswith("_") or not ref_name.endswith("_ref"):
            continue
        ref_fn = getattr(ref_mod, ref_name)
        if not inspect.isfunction(ref_fn) or \
                ref_fn.__module__ != ref_mod.__name__:
            continue
        pub = ref_name[:-len("_ref")]
        ops_fn = getattr(ops_mod, pub, None)
        if ops_fn is None or not callable(ops_fn):
            findings.append(Finding(
                str(ops_path), 1, 0, RPL203,
                f"ref.py declares {ref_name} but ops.py has no public "
                f"'{pub}' wrapper"))
            continue
        want = _math_params(ref_fn)
        got = _math_params(ops_fn)
        if want != got:
            findings.append(Finding(
                str(ops_path), 1, 0, RPL203,
                f"'{pub}' signature drifted from its oracle: ops.py "
                f"takes {got}, ref.py takes {want} (kernel-control "
                f"params excluded)"))
    return findings


def _is_kernel_ops(path: Path) -> bool:
    return path.name == "ops.py" and path.parent.parent.name == "kernels"


def _is_kernel_module(path: Path) -> bool:
    return path.parent.parent.name == "kernels" and \
        path.name in ("kernel.py", "ops.py")


@register_checker("pallas", [RPL201, RPL202, RPL203])
def check(mod: ModuleSource):
    aliases = import_aliases(mod.tree)
    findings: List[Finding] = []

    # map pallas_call sites to their enclosing function
    for fn in [None] + functions(mod.tree):
        scope = fn if fn is not None else mod.tree
        for node in ast.walk(scope):
            if isinstance(node, ast.Call) and \
                    _is_pallas_call(node, aliases):
                # only attribute the call to its innermost function
                if fn is None and any(
                        node in ast.walk(f) for f in functions(mod.tree)):
                    continue
                if fn is not None and any(
                        node in ast.walk(g)
                        for g in functions(fn, nested=True)):
                    continue
                _check_grid_site(mod, node, aliases, findings)
                _check_interpret(mod, node, aliases, fn, findings)

    if _is_kernel_ops(mod.path):
        findings.extend(_check_parity(mod))
    elif mod.path.name == "kernel.py" and _is_kernel_module(mod.path):
        for sibling in ("ops.py", "ref.py"):
            if not mod.path.with_name(sibling).exists():
                findings.append(Finding(
                    str(mod.path), 1, 0, RPL203,
                    f"kernel family has no sibling {sibling}"))
    return findings
