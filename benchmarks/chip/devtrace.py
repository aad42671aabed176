"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is read once into plain tuples (:func:`load`); everything else
here is arithmetic on those tuples, so the tests drive it with small
recorded or synthetic event lists and no accelerator.

On a TPU the profiler writes one plane per chip (``/device:TPU:<i>``)
with an ``XLA Modules`` line (one event per execution of a compiled
program) and an ``XLA Ops`` line (one event per HLO instruction, named
by its HLO text, ``%<instruction> = <shape> <opcode>(...)``).  A
``while`` instruction appears as one event that spans the ops of its
body, so busy time is the union of the *leaf* op intervals.  Host
spans (``jax.profiler.TraceAnnotation`` and the Python tracer) sit on
the ``/host:CPU`` plane, on the same clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

OPEN_MARK = "bench_window_open"
CLOSE_MARK = "bench_window_close"

_INSTR = re.compile(r"%?([A-Za-z_][\w.\-]*) = ")
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclass
class Device:
    name: str
    modules: List[Interval] = field(default_factory=list)
    ops: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    host: List[Interval]                      # host spans, all threads
    window: Tuple[float, float]               # (open_ns, close_ns)


# ----------------------------------------------------------- reading

def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file into a :class:`Trace`.  The window is
    the interval between the two marker annotations the harness emits
    at the chunk boundaries that open and close it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(plane.name)
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.end_ns))
                       for e in line.events]
                if line.name == "XLA Modules":
                    dev.modules = evs
                elif line.name == "XLA Ops":
                    dev.ops = evs
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.end_ns))
                            for e in line.events)
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(devices, host, window_of(host))


def window_of(host: Sequence[Interval]) -> Tuple[float, float]:
    opens = [e for n, s, e in host if n == OPEN_MARK]
    closes = [s for n, s, e in host if n == CLOSE_MARK]
    if not opens or not closes:
        raise ValueError("the trace holds no window markers")
    return max(opens), min(closes)


# ------------------------------------------------------ interval math

def clip(events: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def leaves(ops: Sequence[Interval]) -> List[Interval]:
    """Drop container events (a ``while`` spanning its body's ops): an
    event is a container when another event starts inside it and ends
    no later than it does."""
    srt = sorted(ops, key=lambda x: (x[1], -x[2]))
    out = []
    for i, (n, s, e) in enumerate(srt):
        nxt = srt[i + 1] if i + 1 < len(srt) else None
        if nxt is not None and s <= nxt[1] < e and nxt[2] <= e:
            continue
        out.append((n, s, e))
    return out


def union_ns(events: Sequence[Interval]) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    return union_ns(clip(leaves(dev.ops), lo, hi))


def idle_share(dev: Device, lo: float, hi: float) -> float:
    return 1.0 - busy_ns(dev, lo, hi) / (hi - lo)


# ------------------------------------------------- chunk executions

def main_module(dev: Device, lo: float, hi: float) -> Optional[str]:
    """The program that holds the device longest inside the window: the
    solver's chunk program, whatever the compiler calls it."""
    tot: Dict[str, float] = {}
    for n, s, e in clip(dev.modules, lo, hi):
        tot[n] = tot.get(n, 0.0) + (e - s)
    return max(tot, key=tot.get) if tot else None


def chunk_gaps(dev: Device, lo: float, hi: float) -> List[Interval]:
    """The device-idle stretches between consecutive executions of the
    chunk program that lie inside the window, named by nothing yet
    (``(\"\", start, end)``).  Ops of other programs that run between
    two chunks are busy time, not gap."""
    name = main_module(dev, lo, hi)
    runs = sorted((s, e) for n, s, e in dev.modules
                  if n == name and s >= lo and e <= hi)
    busy = sorted(((s, e) for _, s, e in leaves(dev.ops)),
                  key=lambda x: x[0])
    gaps = []
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        cur = e0
        for s, e in busy:
            if e <= cur or s >= s1:
                continue
            if s > cur:
                gaps.append(("", cur, s))
            cur = max(cur, e)
        if cur < s1:
            gaps.append(("", cur, s1))
    return gaps


def name_gaps(gaps: Sequence[Interval], host: Sequence[Interval]
              ) -> List[Interval]:
    """Name each gap by the innermost host span that covers its middle:
    what the host was doing while the device waited."""
    out = []
    for _, s, e in gaps:
        mid = 0.5 * (s + e)
        inner = None
        for n, hs, he in host:
            if hs <= mid <= he and (inner is None
                                    or he - hs < inner[2] - inner[1]):
                inner = (n, hs, he)
        out.append((inner[0] if inner else "(no host span)", s, e))
    return out


# ---------------------------------------------------------- op names

def instruction(event_name: str) -> str:
    """``%condat_elwise_dual.7 = f32[...] custom-call(...)`` ->
    ``condat_elwise_dual.7``; a bare name passes through."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def base_name(event_name: str) -> str:
    """The instruction without its numeric suffixes or ``vmap_``
    prefixes: the name a ``pallas_call`` was given."""
    name = _SUFFIX.sub("", instruction(event_name))
    while name.startswith("vmap_"):
        name = name[len("vmap_"):]
    return name


def kernel_events(dev: Device, kernel: str, lo: float, hi: float
                  ) -> List[Interval]:
    """The window's executions of the ``pallas_call`` named ``kernel``,
    with or without a ``vmap_`` prefix."""
    return [ev for ev in clip(dev.ops, lo, hi)
            if base_name(ev[0]) == kernel]


def top_ops(devs: Sequence[Device], lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` leaf instructions that took most device time, summed
    over executions and averaged over the chips, in seconds."""
    tot: Dict[str, float] = {}
    for dev in devs:
        for n, s, e in clip(leaves(dev.ops), lo, hi):
            key = instruction(n)
            tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9 / len(devs)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def boundary_idle_ns(dev: Device, lo: float, hi: float) -> List[float]:
    """Device-idle nanoseconds at each boundary between two consecutive
    chunk-program executions inside the window."""
    name = main_module(dev, lo, hi)
    runs = sorted((s, e) for n, s, e in dev.modules
                  if n == name and s >= lo and e <= hi)
    gaps = chunk_gaps(dev, lo, hi)
    out = []
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        out.append(sum(ge - gs for _, gs, ge in gaps
                       if gs >= e0 and ge <= s1))
    return out


def dispatch_gap_ms(tr: Trace) -> Optional[float]:
    """Mean device-idle ms at a chunk boundary, over all the chips."""
    lo, hi = tr.window
    gaps = [g for dev in tr.devices for g in boundary_idle_ns(dev, lo, hi)]
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None


def device_idle_pct(tr: Trace) -> Optional[float]:
    """Percent of the window with no operation on the device, mean over
    the chips."""
    lo, hi = tr.window
    if not any(d.ops for d in tr.devices):
        return None
    return 100.0 * sum(idle_share(d, lo, hi) for d in tr.devices) \
        / len(tr.devices)
