"""Compile the Pallas kernel families for a described TPU v5e chip.

No chip is attached: the TPU compiler builds each kernel for a chip
described by ``topologies.get_topology_desc``, so what Mosaic refuses
(unaligned slices, blocks that overflow the scoped VMEM) fails here,
in the test run, instead of on the chip.  The shapes are the
deployment's: 41x41 stamps, the dual stack of 3072 = 4 scales x 768
stamps, and SCDL's GS shapes (K=4096, P=289 or 81, M=81, A=512).
``solve_many`` runs the kernels under ``vmap`` over its lanes, which
turns per-lane scalars into blocked operands and changes what Mosaic
keeps in VMEM, so the vmapped forms are compiled too.  The Condat
passes are also compiled inside a scanned loop at the population's
ragged sizes (10 000 stamps, 40 000 dual rows), where the compiled
program shows whether they run on the carried arrays in place.

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


# ------------------------------------------- Condat passes in a solver loop
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                    r"([\w\-]+)\((.*?)\)(.*)$")
_MOVES = ("pad", "slice", "concatenate", "copy")


def _instructions(hlo: str):
    """name -> (shape, opcode, operand names, the rest of the line)."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name, shape, op, args, rest = m.groups()
            out[name] = (shape, op, re.findall(r"%([\w.\-]+)", args), rest)
    return out


def _fused_roots(hlo: str):
    """Computation name -> the name of its ROOT instruction."""
    roots, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
        m = re.match(r"^\s*ROOT %([\w.\-]+) = ", line)
        if m and comp:
            roots[comp] = m.group(1)
    return roots


def _origin_op(instrs, roots, name):
    """Opcode that produced ``name``, looking through bitcasts and
    memory-space prefetches (copy-start/done), and into a fusion's
    root."""
    _, op, args, rest = instrs[name]
    while op in ("bitcast", "copy-start", "copy-done", "fusion"):
        if op == "fusion":
            name = roots[re.search(r"calls=%([\w.\-]+)", rest).group(1)]
        else:
            name = args[0]
        _, op, args, rest = instrs[name]
    return op


def _assert_in_place(hlo: str, name: str, rows: int):
    """The condat_elwise custom call reads its array operands straight
    from the loop (no pad, slice, concatenate or copy in front of it),
    and no such op of ``rows`` rows or more sits under ``condat``."""
    instrs, roots = _instructions(hlo), _fused_roots(hlo)
    calls = [k for k, (_, op, _, rest) in instrs.items()
             if op == "custom-call" and name in k
             and "tpu_custom_call" in rest]
    assert len(calls) == 1, calls
    for arg in instrs[calls[0]][2][1:]:          # [0] is the SMEM scalar
        origin = _origin_op(instrs, roots, arg)
        assert origin not in _MOVES, f"{name} takes %{arg} from a {origin}"
    for k, (shape, op, _, rest) in instrs.items():
        if op not in _MOVES or "/condat/" not in rest:
            continue
        dims = re.match(r"\w+\[(\d+)", shape)
        assert not dims or int(dims.group(1)) < rows, \
            f"%{k} ({op} {shape}) under condat"


def _compile_loop(body, sharding, *shapes):
    """``body(carry, consts) -> carry`` scanned 8 times, as a solver's
    chunk program carries its state."""
    def loop(carry, consts):
        return jax.lax.scan(lambda c, _: (body(c, consts), None), carry,
                            None, length=8)[0]
    carry, consts = [[jax.ShapeDtypeStruct(s, jnp.float32,
                                           sharding=sharding)
                      for s in group] for group in shapes]
    return jax.jit(loop).lower(carry, consts).compile().as_text()


@pytest.mark.parametrize("with_xbar", [False, True])
def test_condat_primal_in_place_in_loop(one_chip, with_xbar):
    n = 10000

    def body(carry, consts):
        X, U = carry
        G, t = consts
        with jax.named_scope("condat"):
            out = condat_primal_fwd(X, 0.5 * U, G - X, t,
                                    with_xbar=with_xbar, interpret=False)
            if with_xbar:
                return [out[0], U + t * out[1]]
            return [out, U - t * out]

    hlo = _compile_loop(body, one_chip, [(n, S, S)] * 2, [(n, S, S), ()])
    _assert_kernel(hlo, "condat_elwise_primal")
    _assert_in_place(hlo, "condat_elwise_primal", n)


def test_condat_dual_in_place_in_loop(one_chip):
    m = 4 * 10000

    def body(carry, consts):
        U, C_old = carry
        W, sig = consts
        with jax.named_scope("condat"):
            C_new = 0.9 * C_old + U
            return [condat_dual_fwd(U, C_new, C_old, W, sig,
                                    interpret=False), C_new]

    hlo = _compile_loop(body, one_chip, [(m, S, S)] * 2, [(m, 1, 1), ()])
    _assert_kernel(hlo, "condat_elwise_dual")
    _assert_in_place(hlo, "condat_elwise_dual", m)
