"""Paper Fig. 6 / 11 / 12 — memory-per-worker benchmarks.

Per-device bytes from ``compiled.memory_analysis()`` for the two
use-case steps at N in {3x, 6x} partitions, measured in an 8-device
subprocess (devices are the workers; more partitions => smaller blocks,
the paper's memory/partition trade-off), plus the host-side peak
(``tracemalloc``) of building each bundle — the paper's driver keeps
the full population on the host between dispatches, so host footprint
is part of the per-worker budget.  derived = per-device bytes.

The child runs on the CPU backend (``JAX_PLATFORMS=cpu``) whatever the
parent runs on: on a TPU host the parent may already hold the chip,
and a second process cannot take it.  Its records are labelled
``platform: cpu`` — they are XLA:CPU's memory analysis of eight virtual
CPU devices, not a TPU measurement.

Emits ``BENCH_memory.json`` (uploaded as a CI artifact next to the
other BENCH tables).  ``--smoke`` shrinks both workloads so the whole
subprocess compiles in seconds.

    PYTHONPATH=src python -m benchmarks.bench_memory [--smoke]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import emit, write_bench_json

_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                            + os.environ.get("XLA_FLAGS", ""))
import json
import tracemalloc
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.bundle import Bundle
from repro.core.engine import make_step
from repro.imaging import psf as psf_op
from repro.imaging.condat import SolverConfig
from repro.imaging.deconvolve import build_bundle as psf_bundle, \
    make_step_fn as psf_step
from repro.imaging.scdl import SCDLConfig, build_bundle as scdl_bundle, \
    make_step_fn as scdl_step
from repro.data.synthetic import coupled_patches

SMOKE = {smoke}
out = {{}}
mesh = make_mesh((8,), ("data",))


def measure(name, build, step_fn):
    tracemalloc.start()
    bundle, cfg = build()
    step = make_step(step_fn(cfg), bundle, donate=False)
    c = step.lower(bundle.data, bundle.replicated).compile()
    _, host_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    ma = c.memory_analysis()
    out[name] = dict(args=ma.argument_size_in_bytes,
                     temp=ma.temp_size_in_bytes,
                     output=ma.output_size_in_bytes,
                     host_peak=host_peak)


def build_psf():
    data = psf_op.simulate(48 if SMOKE else 384, jax.random.PRNGKey(1),
                           stamp=16 if SMOKE else 41)
    cfg = SolverConfig(mode="sparse", n_scales=2 if SMOKE else 3)
    bundle, _ = psf_bundle(data.Y, data.psfs, cfg, mesh=mesh,
                           sigma_noise=data.sigma)
    return bundle, cfg


def build_scdl():
    if SMOKE:
        S_h, S_l = coupled_patches(256, 25, 9, 16, seed=3)
        scfg = SCDLConfig(n_atoms=8)
    else:
        S_h, S_l = coupled_patches(4096, 289, 81, 128, seed=3)
        scfg = SCDLConfig(n_atoms=256)
    return scdl_bundle(S_h, S_l, scfg, mesh=mesh), scfg


measure("psf_sparse", build_psf, psf_step)
measure("scdl_gs", build_scdl, scdl_step)
print("JSON" + json.dumps(out))
"""


def run(smoke: bool = False):
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(smoke=smoke)], env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = [l for l in proc.stdout.splitlines()
               if l.startswith("JSON")][0][4:]
    out = json.loads(payload)
    records = []
    for name, d in out.items():
        emit(f"fig6_11_12/{name}_mem_per_worker", 0.0,
             f"args_bytes={d['args']};temp_bytes={d['temp']}")
        records.append({
            "name": f"memory/{name}",
            "device_args_bytes": d["args"],
            "device_temp_bytes": d["temp"],
            "device_output_bytes": d["output"],
            "device_peak_bytes": d["args"] + d["temp"] + d["output"],
            "host_build_peak_bytes": d["host_peak"],
            "platform": "cpu",
            "devices": 8,
            "smoke": smoke,
        })
    write_bench_json("BENCH_memory.json", records)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)
