"""The solver names its layers in the profiler's trace.

Device side: each layer of a deconvolution iteration runs under a
``jax.named_scope``, which the compiled HLO carries in its ``op_name``
metadata.  Host side: both chunk loops emit ``repro.chunk`` spans with
``dispatch``, ``wait``, ``fetch``, ``host`` and ``progress`` children,
all carrying the chunk's start iteration, the parent also whether the
dispatch was the first of its program.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core.driver import IterativeDriver
from repro.core.problem import derive_options, solve, solve_many
from repro.imaging import psf as psf_op
from repro.imaging.condat import SolverConfig
from repro.imaging.deconvolve import DeconvolutionProblem

SCOPES = {"psf_operator", "starlet", "condat", "svt", "objective"}
PARTS = ["repro.chunk", "repro.chunk.dispatch", "repro.chunk.wait",
         "repro.chunk.fetch", "repro.chunk.host", "repro.chunk.progress"]


@pytest.fixture(scope="module")
def stamps():
    d = psf_op.simulate(4, jax.random.PRNGKey(3), stamp=12)
    return d.Y, d.psfs


@pytest.mark.parametrize("mode,absent", [("sparse", "svt"),
                                         ("lowrank", "starlet")])
def test_chunk_program_carries_each_scope(stamps, mode, absent):
    problem = DeconvolutionProblem(SolverConfig(mode=mode, n_scales=2,
                                                rank=2))
    bundle = problem.init_bundle(stamps, None)
    driver = IterativeDriver(problem.full_step, bundle,
                             options=derive_options(
                                 problem, problem.default_options()))
    hlo = driver._scan_step(2).lower(
        bundle.data, bundle.replicated, np.int32(0)).compile().as_text()
    found = {part for path in re.findall(r'op_name="([^"]*)"', hlo)
             for part in path.split("/") if part in SCOPES}
    assert found == SCOPES - {absent}


def _spans(run, tmp):
    """Run ``run()`` under the profiler; the ``repro.chunk*`` host spans
    as (name, start_ns, end_ns, stats) in start order."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("entry", ["solve", "solve_many"])
def test_chunk_loops_emit_their_spans(stamps, entry, tmp_path):
    cfg = SolverConfig(mode="lowrank", rank=2)
    # chunks start at 0, 2, 4; the tail chunk (length 1) compiles anew
    run = dict(cfg=cfg, max_iter=5, chunk=2, tol=0.0,
               progress_fn=lambda ev: None)
    if entry == "solve":
        spans = _spans(lambda: solve("deconvolve", *stamps, **run),
                       tmp_path)
    else:
        spans = _spans(lambda: solve_many("deconvolve",
                                          [stamps, stamps], **run),
                       tmp_path)
    assert [n for n, _, _, _ in spans] == PARTS * 3
    assert [s["chunk"] for _, _, _, s in spans] == \
        [c for c in (0, 2, 4) for _ in PARTS]
    assert [s["first_call"] for n, _, _, s in spans
            if n == "repro.chunk"] == [1, 0, 1]
    assert all("first_call" not in s for n, _, _, s in spans
               if n != "repro.chunk")
    for c in range(3):
        (_, lo, hi, _), *parts = spans[6 * c:6 * c + 6]
        # the parts follow one another inside their chunk's span
        ends = [lo] + [e for _, _, e, _ in parts]
        starts = [s for _, s, _, _ in parts] + [hi]
        assert all(a <= b for a, b in zip(ends, starts))
