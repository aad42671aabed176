"""Shared helpers for the Pallas kernel wrappers.

Besides the backend probe (:func:`auto_interpret`) and padding helper,
this module owns **graceful kernel degradation** (DESIGN.md §18): every
kernel family's public wrapper routes its implementation choice through
:func:`degraded_call`, so on a CPU/GPU host a Pallas construction
failure (or, on any backend, an injected ``kernel`` chaos fault) drops
the family compiled → interpret → ref *once per process*, with a
recorded warning.  On a TPU backend a real kernel failure raises
instead: Mosaic refusing a kernel there is a bug, and running the
Pallas interpreter on the chip would only hide it.

:func:`reference_kernels` routes every family to its pure-jnp oracle
for a scope — the plain reference that on-chip checks compare the
kernel path against.
"""
from __future__ import annotations

import contextlib
import math
import os
import threading
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.resilience import chaos as _chaos
from repro.resilience.errors import InjectedFault


def auto_interpret() -> bool:
    """Compile the Mosaic kernel on TPU; fall back to interpreter mode
    everywhere else (CPU/GPU hosts run the same traced jnp ops).

    ``REPRO_FORCE_INTERPRET=1`` overrides the backend probe and forces
    interpreter mode even on TPU — the escape hatch for debugging a
    Mosaic miscompile or bisecting kernel-vs-oracle divergence on
    hardware (set to ``0``/``false``/empty to disable; any other value
    forces).  The env var is read per call, so tests can monkeypatch
    it without re-importing kernel modules.
    """
    forced = os.environ.get("REPRO_FORCE_INTERPRET", "")
    if forced.strip().lower() not in ("", "0", "false", "no"):
        return True
    return jax.default_backend() != "tpu"


# Mosaic's default scoped-VMEM limit per kernel on TPU v5e.  Derived
# block sizes spend three quarters of it and leave the rest to Mosaic's
# own scratch.
SCOPED_VMEM_BYTES = 16 << 20
_VMEM_BUDGET = SCOPED_VMEM_BYTES * 3 // 4


def vmem_rows(tail, live: int, cap: int = 128) -> int:
    """Leading-axis block size for a kernel over ``(rows, *tail)`` blocks.

    ``live`` is how many such blocks the kernel body keeps in VMEM at
    once (its fp32 values and temporaries, as Mosaic allocates them).
    Each row of a block occupies ``prod(tail[:-2])`` fp32 tiles of the
    last two axes padded to the TPU's (8, 128) layout — a 41x41 stamp
    takes a 48x128 tile, 3.2x its own size.  Returns the largest
    multiple of 8 in ``[8, cap]`` that fits the budget.
    """
    *lead, h, w = tail
    row = math.prod(lead) * (-(-h // 8) * 8) * (-(-w // 128) * 128) * 4
    rows = _VMEM_BUDGET // (live * row)
    return max(8, min(cap, rows // 8 * 8))


def pad_leading(arrays, block: int):
    """Zero-pad a shared leading axis to a whole number of ``block``
    rows.  Returns the padded list and the padded length.

    ``dict_outer`` needs it: it accumulates Grams over rows, so every
    row a block reads must be real or zero, and a ragged last block
    would read undefined rows into the sum.  ``admm_elwise`` uses it
    too (its pad rows produce pad rows).  The purely per-row
    ``condat_elwise`` passes use a ragged last block instead and copy
    nothing."""
    n = arrays[0].shape[0]
    pad = -n % block
    if pad:
        arrays = [jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) for a in arrays]
    return arrays, n + pad


# ---------------------------------------------------------------------------
# Graceful kernel degradation: compiled -> interpret -> ref, once per family.
# ---------------------------------------------------------------------------

# Per-family degradation level.  Absent = 0 (honour the caller's request);
# 1 = force interpret mode; 2 = force the pure-jnp reference path.  The dict
# is process-global on purpose: once a family's compiled kernel has failed,
# every later call in the process (including other solves) skips straight to
# the surviving level instead of re-failing per call.
_DEGRADED: Dict[str, int] = {}
_FALLBACK_EVENTS: List[dict] = []
_LOCK = threading.Lock()

# set inside ``reference_kernels()``: every family runs its oracle
_REFERENCE = False

_LEVEL_NAMES = ("compiled", "interpret", "ref")


def kernel_fallbacks() -> Tuple[dict, ...]:
    """Degradation events recorded so far (process lifetime), oldest
    first.  ``Supervisor.finalize`` slices off the per-run suffix for
    ``Solution.recovery``."""
    return tuple(_FALLBACK_EVENTS)


def reset_degradation() -> None:
    """Forget all degradation state and events (test isolation)."""
    with _LOCK:
        _DEGRADED.clear()
        _FALLBACK_EVENTS.clear()


@contextlib.contextmanager
def reference_kernels():
    """Run every kernel family's pure-jnp reference inside the scope
    (process-wide, so solves on a serving thread follow it too), as
    ``use_kernel=False`` would.  The choice is made at trace time: jit
    the reference computation inside the scope."""
    global _REFERENCE
    prev, _REFERENCE = _REFERENCE, True
    try:
        yield
    finally:
        _REFERENCE = prev


def _degrade(family: str, level: int, exc: BaseException) -> None:
    with _LOCK:
        if _DEGRADED.get(family, 0) < level:
            _DEGRADED[family] = level
            event = {"family": family,
                     "to": _LEVEL_NAMES[level],
                     "error": f"{type(exc).__name__}: {exc}"}
            _FALLBACK_EVENTS.append(event)
            warnings.warn(
                f"kernel family {family!r} degraded to "
                f"{_LEVEL_NAMES[level]} after "
                f"{type(exc).__name__}: {exc}", RuntimeWarning,
                stacklevel=3)


def degraded_call(family: str, *, kernel: Callable[[bool], Any],
                  ref: Callable[[], Any],
                  requested_interpret: Optional[bool] = None) -> Any:
    """Run a kernel family's implementation at the highest level that
    still works: compiled Mosaic, then interpreter mode, then the pure
    jnp reference — degrading the *family* (not the call) on the first
    failure, with a recorded ``RuntimeWarning``.

    ``kernel(interpret)`` must build-and-call the Pallas path;
    ``ref()`` the reference path.  Only errors raised at Python level
    are catchable — kernel *construction*/trace/lowering failures and
    injected ``kernel`` chaos faults.  A Mosaic abort inside an already
    compiled program surfaces at the dispatch host sync instead, where
    the resilience supervisor's retry loop owns it (DESIGN.md §18).

    ``requested_interpret=None`` defers to :func:`auto_interpret`;
    explicit True counts as starting at the interpret level.

    On a TPU backend only injected faults degrade; any other failure
    propagates, so a kernel Mosaic refuses fails the run loudly.
    """
    if _REFERENCE:
        return ref()
    interpret = (auto_interpret() if requested_interpret is None
                 else requested_interpret)
    strict = jax.default_backend() == "tpu"
    level = _DEGRADED.get(family, 0)
    if level == 0 and not interpret:
        try:
            _chaos.maybe_raise("kernel", tag=family)
            return kernel(False)
        except Exception as e:  # degrade the family, not the run
            if strict and not isinstance(e, InjectedFault):
                raise
            _degrade(family, 1, e)
            level = 1
    if level <= 1:
        try:
            _chaos.maybe_raise("kernel", tag=family)
            return kernel(True)
        except Exception as e:  # last resort: the jnp reference
            if strict and not isinstance(e, InjectedFault):
                raise
            _degrade(family, 2, e)
    return ref()
