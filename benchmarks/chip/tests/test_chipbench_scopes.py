"""The per-layer readers of the program's own names: device time under
each named scope of the solver, and the host's critical path at a
chunk boundary; on synthetic event lists, a synthetic profiler file and
a recorded TPU trace; no accelerator."""
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import devtrace  # noqa: E402
import scopes  # noqa: E402
from devtrace import Device, Trace  # noqa: E402

BODY = "jit(chunk_fn)/jit(main)/while/body"


@pytest.mark.parametrize("path,scope", [
    (f"{BODY}/condat/psf_operator/dot_general", "psf_operator"),
    (f"{BODY}/condat/starlet/jit(_smooth_kernel)/starlet2d_smooth",
     "starlet"),
    (f"{BODY}/condat/pad", "condat"),
    (f"{BODY}/objective/reduce_sum", "objective"),
    (f"{BODY}/condat/svt/jit(eigh)/eigh", "svt"),
    # a transform wraps the scope it runs
    ("jit(f)/vmap(condat)/psf_operator/mul", "psf_operator"),
    ("jit(f)/transpose(jvp(condat))/mul", "condat"),
    # fused instructions join their op names with ';'
    (f"{BODY}/objective/abs;{BODY}/condat/sub", "objective"),
    # whole components only
    (f"{BODY}/condat_elwise/add", None),
    (f"{BODY}/my_svt/add", None),
    (f"{BODY}/add", None),
    ("", None),
])
def test_the_innermost_whole_scope_names_an_op(path, scope):
    assert scopes.scope_of(path) == scope


@pytest.fixture
def given_paths(monkeypatch):
    """Op paths handed to the scope readers in place of the ones they
    would read from the trace's file, by trace."""
    table = {}
    monkeypatch.setattr(scopes, "scoped_ops", lambda tr: table[id(tr)])
    return table


def _reading(given_paths, scoped, iters=2, window=(0, 100), host=()):
    dev = Device("/device:TPU:0", [], [("", s, e) for _, s, e in scoped])
    tr = Trace([dev], list(host), window)
    given_paths[id(tr)] = {dev.name: list(scoped)}
    return SimpleNamespace(trace=tr, iters=iters)


def _read(metric, reading):
    return cells.component("metrics", metric).read(reading)


def test_scope_readers_split_leaf_time_per_iteration(given_paths):
    scoped = [(f"{BODY}", 0, 22e6),                     # the while
              (f"{BODY}/condat/psf_operator/dot", 0, 10e6),
              (f"{BODY}/condat/pad", 10e6, 14e6),
              (f"{BODY}/condat/starlet/sub", 14e6, 20e6),
              (f"{BODY}/objective/reduce", 20e6, 21e6),
              (f"{BODY}/add", 21e6, 22e6),              # scan plumbing
              (f"{BODY}/condat/pad", 90e6, 130e6)]      # cut by window
    reading = _reading(given_paths, scoped, iters=2, window=(0, 100e6))
    got = {s: _read(f"scope_ms.{s}", reading) for s in scopes.SCOPES}
    assert got.pop("svt") is None
    assert got == pytest.approx({"psf_operator": 5.0, "condat": 7.0,
                                 "starlet": 3.0, "objective": 0.5})


def test_scope_readers_find_nothing_in_an_unscoped_program(given_paths):
    reading = _reading(given_paths, [(f"{BODY}/add", 0, 10), ("", 10, 20)])
    assert all(_read(f"scope_ms.{s}", reading) is None
               for s in scopes.SCOPES)


def test_boundary_host_ms_runs_from_wait_end_to_next_dispatch_end(
        given_paths):
    ms = 1e6
    host = [(devtrace.OPEN_MARK, 0, 1),
            ("repro.chunk.dispatch", 2 * ms, 3 * ms),
            ("repro.chunk.wait", 3 * ms, 50 * ms),
            ("repro.chunk.fetch", 50 * ms, 51 * ms),
            ("repro.chunk.dispatch", 52 * ms, 53 * ms),
            ("repro.chunk.wait", 53 * ms, 100 * ms),
            ("repro.chunk.dispatch", 104 * ms, 105 * ms),
            ("repro.chunk.wait", 105 * ms, 150 * ms),
            (devtrace.CLOSE_MARK, 151 * ms, 152 * ms),
            # outside the window
            ("repro.chunk.dispatch", 160 * ms, 161 * ms)]
    reading = _reading(given_paths, [], window=(1, 151 * ms), host=host)
    assert _read("boundary_host_ms", reading) == pytest.approx(4.0)
    assert _read("boundary_host_ms", _reading(
        given_paths, [], window=(1, 151 * ms),
        host=host[:1] + host[-2:])) is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 2000 }
    events { metadata_id: 3 offset_ps: 7000 duration_ps: 1000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 4 offset_ps: 0 duration_ps: 9000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[] fusion()"
    stats { metadata_id: 10
            str_value: "jit(f)/condat/psf_operator/mul:mul" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[] copy()"
    stats { metadata_id: 11 str_value: "elsewhere" }
    stats { metadata_id: 10 ref_value: 12 } } }
  event_metadata { key: 3 value { id: 3 name: "%dot.3 = f32[] dot()" } }
  event_metadata { key: 4 value { id: 4 name: "jit_f(1)" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "long_name" } }
  stat_metadata { key: 12 value { id: 12 name: "jit(f)/objective/abs" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
          events { metadata_id: 1 offset_ps: 0 duration_ps: 10 }
          events { metadata_id: 2 offset_ps: 0 duration_ps: 1000 }
          events { metadata_id: 3 offset_ps: 10000000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "repro.chunk" } }
  event_metadata { key: 2 value { id: 2 name: "bench_window_open" } }
  event_metadata { key: 3 value { id: 3 name: "bench_window_close" } } }
"""


def _write(path, text):
    from jax.profiler import ProfileData
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_op_paths_read_the_tf_op_stat_of_each_ops_metadata(tmp_path):
    got = scopes.op_paths(_write(tmp_path / "t.xplane.pb", XSPACE))
    assert got == {"/device:TPU:0": [
        ("jit(f)/condat/psf_operator/mul:mul", 1000.0, 1005.0),
        ("jit(f)/objective/abs", 1005.0, 1007.0),
        ("", 1007.0, 1008.0)]}


def test_scope_readers_read_the_file_their_trace_came_from(tmp_path,
                                                           monkeypatch):
    # run.py profiles into a chipbench_* directory of the temporary one
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    mine = _write(tmp_path / "chipbench_a" / "plugins" / "h.xplane.pb",
                  XSPACE)
    newer = _write(tmp_path / "chipbench_b" / "h.xplane.pb",
                   XSPACE.replace('"XLA Ops" timestamp_ns: 1000',
                                  '"XLA Ops" timestamp_ns: 3000'))
    os.utime(newer, (time.time() + 60,) * 2)
    elsewhere = _write(tmp_path / "other" / "h.xplane.pb", XSPACE)
    reading = SimpleNamespace(trace=devtrace.load(mine), iters=1)
    assert scopes.trace_file(reading.trace) == mine
    got = {s: _read(f"scope_ms.{s}", reading) for s in scopes.SCOPES}
    assert got == {"psf_operator": pytest.approx(5e-6), "starlet": None,
                   "condat": None, "svt": None,
                   "objective": pytest.approx(2e-6)}
    # a trace whose file is not where run.py writes: nothing to read
    shutil.rmtree(tmp_path / "chipbench_a")
    reading = SimpleNamespace(trace=devtrace.load(elsewhere), iters=1)
    assert scopes.trace_file(reading.trace) is None
    assert all(_read(f"scope_ms.{s}", reading) is None
               for s in scopes.SCOPES)


def _recorded(given_paths):
    rec = json.loads((Path(__file__).parent
                      / "recorded_scoped_trace.json").read_text())
    devs, paths = [], {}
    for d in rec["devices"]:
        ops = [(name, s, e) for name, _, s, e in d["ops"]]
        paths[d["name"]] = [(d["paths"][i], s, e) for _, i, s, e in d["ops"]]
        devs.append(Device(d["name"], [tuple(m) for m in d["modules"]], ops))
    tr = Trace(devs, [tuple(h) for h in rec["host"]], tuple(rec["window"]))
    given_paths[id(tr)] = paths
    return SimpleNamespace(trace=tr, iters=rec["iters"])


def test_recorded_trace_splits_its_busy_time_among_the_scopes(given_paths):
    reading = _recorded(given_paths)
    lo, hi = reading.trace.window
    busy_ms = devtrace.busy_ns(reading.trace.devices[0], lo, hi) * 1e-6 \
        / reading.iters
    got = {s: _read(f"scope_ms.{s}", reading) for s in scopes.SCOPES}
    # sparse mode: no SVT; the other four take nearly all the busy time
    assert got.pop("svt") is None
    assert all(v > 0 for v in got.values())
    assert 0.9 * busy_ms < sum(got.values()) <= busy_ms
    assert got["starlet"] > got["condat"] > got["psf_operator"] \
        > got["objective"]


def test_recorded_trace_reads_the_host_path_at_its_chunk_boundary(
        given_paths):
    reading = _recorded(given_paths)
    host_ms = _read("boundary_host_ms", reading)
    assert 0 < host_ms < devtrace.dispatch_gap_ms(reading.trace)
