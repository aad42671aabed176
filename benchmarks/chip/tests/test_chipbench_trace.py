"""Trace reduction, roofline arithmetic and the peaks table, on small
synthetic event lists and on a recorded TPU trace; no accelerator."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import roofline  # noqa: E402
from devtrace import Device, Trace  # noqa: E402


def test_union_merges_overlaps_and_skips_holes():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert devtrace.union_ns(evs) == 25
    assert devtrace.union_ns([]) == 0


def test_leaves_drop_a_while_that_spans_its_body():
    ops = [("%while.4 = (s32[])", 0, 100), ("%a.1 = f32[]", 0, 40),
           ("%b.2 = f32[]", 50, 90), ("%c.3 = f32[]", 100, 110)]
    assert [n for n, _, _ in devtrace.leaves(ops)] == \
        ["%a.1 = f32[]", "%b.2 = f32[]", "%c.3 = f32[]"]


def test_idle_share_clips_to_the_window():
    dev = Device("/device:TPU:0", [], [("%a = x", -50, 30),
                                       ("%b = x", 60, 80)])
    assert devtrace.idle_share(dev, 0, 100) == pytest.approx(0.5)


def test_chunk_gaps_count_only_idle_time_between_chunk_programs():
    mods = [("jit_chunk_fn(1)", 0, 100), ("jit_small(2)", 105, 108),
            ("jit_chunk_fn(1)", 110, 210), ("jit_chunk_fn(1)", 230, 330)]
    ops = [("%k.1 = x", 0, 100), ("%s.1 = x", 105, 108),
           ("%k.1 = x", 110, 210), ("%k.1 = x", 230, 330)]
    dev = Device("/device:TPU:0", mods, ops)
    assert devtrace.main_module(dev, 0, 400) == "jit_chunk_fn(1)"
    gaps = devtrace.chunk_gaps(dev, 0, 400)
    assert [(s, e) for _, s, e in gaps] == [(100, 105), (108, 110),
                                           (210, 230)]
    assert devtrace.boundary_idle_ns(dev, 0, 400) == [7, 20]
    host = [("outer", 0, 400), ("sync", 200, 240)]
    assert [n for n, _, _ in devtrace.name_gaps(gaps, host)] == \
        ["outer", "outer", "sync"]


@pytest.mark.parametrize("name", [
    "%starlet2d_smooth.82 = f32[10032,41,41]{2,1,0} custom-call(...)",
    "%vmap_starlet2d_smooth.3 = f32[4,192,41,41] custom-call(...)",
    "starlet2d_smooth",
    "%vmap_vmap_starlet2d_smooth.1.2 = f32[] custom-call(...)",
])
def test_kernels_match_with_and_without_vmap(name):
    dev = Device("d", [], [(name, 0, 1)])
    assert len(devtrace.kernel_events(dev, "starlet2d_smooth", 0, 2)) == 1


@pytest.mark.parametrize("name", [
    "%starlet2d_smooth_fused.1 = f32[] fusion(...)",
    "%condat_elwise_dual.5 = (f32[40032,41,41]) custom-call(...)",
])
def test_kernel_names_do_not_match_prefixes(name):
    dev = Device("d", [], [(name, 0, 1)])
    assert not devtrace.kernel_events(dev, "starlet2d_smooth", 0, 2)
    assert not devtrace.kernel_events(dev, "condat_elwise", 0, 2)


def test_window_comes_from_the_markers():
    host = [(devtrace.OPEN_MARK, 10, 12), ("x", 0, 100),
            (devtrace.CLOSE_MARK, 90, 91)]
    assert devtrace.window_of(host) == (12, 90)
    with pytest.raises(ValueError):
        devtrace.window_of([("x", 0, 1)])


def _recorded():
    rec = json.loads((Path(__file__).parent
                      / "recorded_sparse_trace.json").read_text())
    devs = [Device(d["name"], [tuple(m) for m in d["modules"]],
                   [tuple(o) for o in d["ops"]]) for d in rec["devices"]]
    return Trace(devs, [tuple(h) for h in rec["host"]],
                 tuple(rec["window"]))


def test_recorded_trace_reduces_to_plausible_numbers():
    tr = _recorded()
    lo, hi = tr.window
    dev = tr.devices[0]
    # three executions of the 8-iteration chunk program, 11 starlet
    # smoothings and one dual clamp per iteration
    assert devtrace.main_module(dev, lo, hi).startswith("jit_chunk_fn")
    assert len(devtrace.kernel_events(dev, "starlet2d_smooth", lo, hi)) \
        == 3 * 8 * 11
    assert len(devtrace.kernel_events(dev, "condat_elwise_dual", lo, hi)) \
        == 3 * 8
    gaps = devtrace.boundary_idle_ns(dev, lo, hi)
    assert len(gaps) == 2 and all(0 < g < 1e7 for g in gaps)
    assert 0 < devtrace.idle_share(dev, lo, hi) < 0.05
    top = devtrace.top_ops(tr.devices, lo, hi, k=3)
    assert top[0][0] == "condat_elwise_dual.7" and top[0][1] > 0


def _cell(**sizes):
    return SimpleNamespace(local_records=sizes.pop("n"), sizes=sizes,
                           traffic={"solver": {"mode": sizes.pop("mode",
                                                                 "sparse")}})


def test_family_work_at_hand_counted_shapes():
    star = roofline.family("starlet2d").KERNELS["starlet2d_smooth"]
    # 2 stamps of 3x3: 18 pixels, 20 flops and 8 bytes each
    assert star(_cell(n=2, stamp=3)) == (360, 144)
    cond = roofline.family("condat_elwise").KERNELS
    assert cond["condat_elwise_primal"](_cell(n=2, stamp=3)) == (90, 288)
    assert cond["condat_elwise_primal"](
        _cell(n=2, stamp=3, mode="lowrank")) == (126, 360)
    # 2 stamps x 4 scales = 8 planes of 9 pixels, plus one weight each
    assert cond["condat_elwise_dual"](_cell(n=2, stamp=3, n_scales=4)) \
        == (432, 8 * 9 * 16 + 32)


def test_roofline_share_from_events():
    peak = roofline.peaks("TPU v5 lite")
    cell = _cell(n=10000, stamp=41)
    flops, nbytes = roofline.family("starlet2d").KERNELS[
        "starlet2d_smooth"](cell)
    least, side = roofline.least_seconds(flops, nbytes, peak)
    assert side == "memory"
    ev = "%starlet2d_smooth.1 = f32[10032,41,41] custom-call(...)"
    dt_ns = 4 * least * 1e9
    dev = Device("/device:TPU:0", [], [(ev, 0, dt_ns), (ev, dt_ns, 2 * dt_ns)])
    reading = SimpleNamespace(trace=Trace([dev], [], (0, 2 * dt_ns)),
                              cell=cell, device_kind="TPU v5 lite")
    assert roofline.share(reading, "starlet2d") == pytest.approx(25.0)
    assert roofline.share(reading, "condat_elwise") is None


def test_peaks_refuse_an_unknown_chip():
    assert roofline.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
