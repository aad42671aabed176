"""Compile the Pallas kernel families for a described TPU v5e chip.

No chip is attached: the TPU compiler builds each kernel for a chip
described by ``topologies.get_topology_desc``, so what Mosaic refuses
(unaligned slices, blocks that overflow the scoped VMEM) fails here,
in the test run, instead of on the chip.  The shapes are the
deployment's: 41x41 stamps, the dual stack of 3072 = 4 scales x 768
stamps, and SCDL's GS shapes (K=4096, P=289 or 81, M=81, A=512).
``solve_many`` runs the kernels under ``vmap`` over its lanes, which
turns per-lane scalars into blocked operands and changes what Mosaic
keeps in VMEM, so the vmapped forms are compiled too.

The topology is described inside a module fixture — never while a
module is imported — because only one process may hold the TPU
library at a time; the fixture skips where it cannot be described.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.admm_elwise.kernel import admm_elwise_fwd
from repro.kernels.condat_elwise.kernel import (condat_dual_fwd,
                                                condat_primal_fwd)
from repro.kernels.dict_outer.kernel import (dict_outer_fwd,
                                             dict_outer_pair_fwd)
from repro.kernels.starlet2d.kernel import smooth_fwd

S = 41


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent
    compilation cache off: an entry written for a described chip cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, sharding, *shapes, lanes=None):
    if lanes:
        fn = jax.vmap(fn)
        shapes = [(lanes,) + s for s in shapes]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str, name: str):
    # under vmap the instruction is named "vmap_<name>"
    pat = (r"%[\w.\-]*" + name + r"[\w.\-]* = [^\n]*"
           r'custom_call_target="tpu_custom_call"')
    assert re.search(pat, hlo), \
        f"{name} is not a Mosaic custom call in the compiled HLO"


@pytest.mark.parametrize("scale", [0, 2])
def test_starlet2d_compiles(one_chip, scale):
    hlo = _compile(lambda x: smooth_fwd(x, scale, interpret=False),
                   one_chip, (1024, S, S))
    _assert_kernel(hlo, "starlet2d_smooth")


@pytest.mark.parametrize("with_xbar", [False, True])
def test_condat_primal_compiles(one_chip, with_xbar):
    hlo = _compile(
        lambda x, u, g, t: condat_primal_fwd(x, u, g, t,
                                             with_xbar=with_xbar,
                                             interpret=False),
        one_chip, (1024, S, S), (1024, S, S), (1024, S, S), ())
    _assert_kernel(hlo, "condat_elwise_primal")


def test_condat_dual_compiles(one_chip):
    m = 4 * 768
    hlo = _compile(
        lambda u, cn, co, w, s: condat_dual_fwd(u, cn, co, w, s,
                                                interpret=False),
        one_chip, (m, S, S), (m, S, S), (m, S, S), (m, 1, 1), ())
    _assert_kernel(hlo, "condat_elwise_dual")


def test_dict_outer_compiles(one_chip):
    hlo = _compile(lambda s, w: dict_outer_fwd(s, w, interpret=False),
                   one_chip, (4096, 81), (4096, 512))
    _assert_kernel(hlo, "dict_outer")


@pytest.mark.parametrize("P", [81, 289])
def test_dict_outer_pair_compiles(one_chip, P):
    hlo = _compile(
        lambda sh, sl, wh, wl: dict_outer_pair_fwd(sh, sl, wh, wl,
                                                   interpret=False),
        one_chip, (4096, P), (4096, 81), (4096, 512), (4096, 512))
    _assert_kernel(hlo, "dict_outer_pair")


def test_admm_elwise_compiles(one_chip):
    hlo = _compile(
        lambda wh, wl, yz: admm_elwise_fwd(wh, wl, yz, c1=0.4, c2=0.4,
                                           c3=0.8, t1=0.025, t2=0.025,
                                           interpret=False),
        one_chip, (4096, 512), (4096, 512), (4096, 5, 512))
    _assert_kernel(hlo, "admm_elwise")


@pytest.mark.parametrize("with_xbar", [False, True])
def test_condat_primal_compiles_vmapped(one_chip, with_xbar):
    # per-lane step sizes: the scalar operand gains a lane axis
    hlo = _compile(
        lambda x, u, g, t: condat_primal_fwd(x, u, g, t,
                                             with_xbar=with_xbar,
                                             interpret=False),
        one_chip, (192, S, S), (192, S, S), (192, S, S), (), lanes=4)
    _assert_kernel(hlo, "condat_elwise_primal")


def test_condat_dual_compiles_vmapped(one_chip):
    m = 4 * 192
    hlo = _compile(
        lambda u, cn, co, w, s: condat_dual_fwd(u, cn, co, w, s,
                                                interpret=False),
        one_chip, (m, S, S), (m, S, S), (m, S, S), (m, 1, 1), (),
        lanes=4)
    _assert_kernel(hlo, "condat_elwise_dual")


def test_admm_elwise_compiles_vmapped(one_chip):
    hlo = _compile(
        lambda wh, wl, yz: admm_elwise_fwd(wh, wl, yz, c1=0.4, c2=0.4,
                                           c3=0.8, t1=0.025, t2=0.025,
                                           interpret=False),
        one_chip, (4096, 512), (4096, 512), (4096, 5, 512), lanes=2)
    _assert_kernel(hlo, "admm_elwise")
