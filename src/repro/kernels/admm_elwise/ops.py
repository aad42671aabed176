"""Public wrapper for the fused ADMM elementwise tail.

``use_kernel=None`` auto-selects: the Pallas kernel where it compiles to
Mosaic (TPU), the pure-jnp oracle elsewhere — on CPU/GPU hosts the
stacked-state oracle already collapses to one fused XLA loop, and the
interpreter would only add overhead inside the training scan.  Tests
pass ``use_kernel=True`` to exercise the kernel in interpreter mode on
any backend.

The kernel path routes through ``kernels.common.degraded_call``, so a
Pallas failure degrades the ``admm_elwise`` family compiled → interpret
→ ref once per process with a recorded warning (DESIGN.md §18).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.admm_elwise.kernel import admm_elwise_fwd
from repro.kernels.common import auto_interpret, degraded_call
from repro.kernels.admm_elwise.ref import admm_elwise_ref

FAMILY = "admm_elwise"


@partial(jax.jit, static_argnames=("c1", "c2", "c3", "t1", "t2",
                                   "block_k", "interpret"))
def _admm_kernel(Wh, Wl, YZ, *, c1, c2, c3, t1, t2, block_k,
                 interpret: bool):
    return admm_elwise_fwd(Wh, Wl, YZ, c1=c1, c2=c2, c3=c3,
                           t1=t1, t2=t2, block_k=block_k,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("c1", "c2", "c3", "t1", "t2"))
def _admm_ref(Wh, Wl, YZ, *, c1, c2, c3, t1, t2):
    return admm_elwise_ref(Wh, Wl, YZ, c1=c1, c2=c2, c3=c3,
                           t1=t1, t2=t2)


def admm_elwise(Wh, Wl, YZ, *, c1, c2, c3, t1, t2,
                use_kernel=None, block_k=None, interpret=None):
    if use_kernel is None:
        use_kernel = not auto_interpret()
    if not use_kernel:
        return _admm_ref(Wh, Wl, YZ, c1=c1, c2=c2, c3=c3, t1=t1, t2=t2)
    return degraded_call(
        FAMILY,
        kernel=lambda interp: _admm_kernel(
            Wh, Wl, YZ, c1=c1, c2=c2, c3=c3, t1=t1, t2=t2,
            block_k=block_k, interpret=interp),
        ref=lambda: _admm_ref(Wh, Wl, YZ, c1=c1, c2=c2, c3=c3,
                              t1=t1, t2=t2),
        requested_interpret=interpret)
