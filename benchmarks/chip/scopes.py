"""Device time under the solver's named scopes (``jax.named_scope`` in the
program).

On the chip an op's scope path, its HLO ``op_name``
(``jit(chunk_fn)/while/body/condat/psf_operator/dot_general``), is the
``tf_op`` stat of its ``XLA Ops`` event's metadata, written as
``<op_name>:``.  The profiler's Python reader, which :func:`devtrace.load`
uses, shows an event's own stats only, so the device planes are read a
second time here, from the protobuf wire format of the ``.xplane.pb``
file: XSpace, in tsl/profiler/protobuf/xplane.proto, whose field numbers
these are.

A per-layer reader gets the loaded :class:`devtrace.Trace` and not its
file.  ``run.py`` writes the trace under a ``chipbench_*`` directory of
the temporary directory and removes it only after the readers ran, so
:func:`trace_file` looks there and takes the newest file whose ops are
those of the trace.
"""
from __future__ import annotations

import glob
import os
import re
import tempfile
from typing import Dict, List, Optional, Tuple

from devtrace import Interval, Trace, clip, leaves

# the solver's named scopes, each a component of an op's op_name path
SCOPES = ("psf_operator", "starlet", "condat", "svt", "objective")
PATH_STAT = "tf_op"
_WRAPPER = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")


def scope_of(path: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` that is a whole component of the
    op_name ``path``.  A transform wraps the scope it runs
    (``vmap(condat)``, ``transpose(jvp(condat))``) and still names it;
    ``condat_x`` or ``my_condat`` name nothing.  None outside every
    scope."""
    for part in reversed(path.split(";")[0].split("/")):
        while (m := _WRAPPER.match(part)):
            part = m.group(1)
        if part in SCOPES:
            return part
    return None


def scope_ms(tr: Trace, scope: str, iters: int) -> Optional[float]:
    """Device ms per solver iteration of the window's leaf ops whose
    innermost scope is ``scope`` (:func:`scope_of`), averaged over the
    chips.  None where no op of the window falls under it, as in a
    program that names no scopes."""
    if not iters:
        return None
    lo, hi = tr.window
    paths = scoped_ops(tr)
    ns, found = 0.0, False
    for dev in tr.devices:
        for path, s, e in clip(leaves(paths.get(dev.name, [])), lo, hi):
            if scope_of(path) == scope:
                ns += e - s
                found = True
    return ns * 1e-6 / iters / len(tr.devices) if found else None


# ------------------------------------------------- finding the file

_SEEN: List[Tuple[Trace, Dict[str, List[Interval]]]] = []


def scoped_ops(tr: Trace) -> Dict[str, List[Interval]]:
    """Each device's ``XLA Ops`` events of ``tr`` as (op path, start_ns,
    end_ns), by device name; empty where the trace's file is not found.
    Read once per trace."""
    if not (_SEEN and _SEEN[0][0] is tr):
        path = trace_file(tr)
        _SEEN[:] = [(tr, op_paths(path) if path else {})]
    return _SEEN[0][1]


def trace_file(tr: Trace) -> Optional[str]:
    """The newest ``.xplane.pb`` under the temporary directory's
    ``chipbench_*`` directories whose device ops are those of ``tr``."""
    if not tr.devices:
        return None
    found = glob.glob(os.path.join(tempfile.gettempdir(), "chipbench_*",
                                   "**", "*.xplane.pb"), recursive=True)
    for path in sorted(found, key=_mtime, reverse=True):
        try:
            if _same_ops(tr, op_paths(path)):
                return path
        except OSError:                  # removed by its own run
            continue
    return None


def _mtime(path: str) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:
        return 0.0


def _same_ops(tr: Trace, paths: Dict[str, List[Interval]]) -> bool:
    """Whether ``paths`` holds the ops of every device of ``tr``, at the
    same times to within the profiler's rounding of a nanosecond."""
    for dev in tr.devices:
        mine = paths.get(dev.name)
        if mine is None or len(mine) != len(dev.ops):
            return False
        if any(abs(s - s2) > 1 or abs(e - e2) > 1
               for (s, e), (s2, e2) in zip(sorted(x[1:] for x in dev.ops),
                                           sorted(x[1:] for x in mine))):
            return False
    return True


# ------------------------------------------- the protobuf wire format

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) pairs of one protobuf message: an int for
    a varint or fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            w = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + w], "little"), i + w
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield num, v


def _text(v) -> str:
    return bytes(v).decode(errors="replace")


def _sub(buf, *nums):
    """The length-delimited values found by following field numbers
    ``nums`` down through nested messages."""
    if not nums:
        yield buf
        return
    for num, v in _fields(buf):
        if num == nums[0]:
            yield from _sub(v, *nums[1:])


def _plane_paths(plane) -> List[Interval]:
    """One device plane's ``XLA Ops`` events as (op path, start_ns,
    end_ns)."""
    names = {}
    for md in _sub(plane, 5, 2):                 # stat metadata
        f = dict(_fields(md))
        names[f.get(1, 0)] = _text(f.get(2, b""))
    paths = {}
    for md in _sub(plane, 4, 2):                 # event metadata
        mid, path = 0, ""
        for num, v in _fields(md):
            if num == 1:
                mid = v
            elif num == 5:                       # a stat: str or ref
                st = dict(_fields(v))
                if names.get(st.get(1)) == PATH_STAT:
                    path = (_text(st[5]) if 5 in st
                            else names.get(st.get(7), ""))
        paths[mid] = path
    out = []
    for line in _sub(plane, 3):
        f = dict(_fields(line))
        if _text(f.get(2, b"")) != "XLA Ops":
            continue
        for ev in _sub(line, 4):
            e = dict(_fields(ev))
            # whole nanoseconds, as the profiler's reader gives them
            s = float(f.get(3, 0) + e.get(2, 0) // 1000)
            out.append((paths.get(e.get(1, 0), ""), s,
                        s + e.get(3, 0) // 1000))
    return out


def op_paths(path: str) -> Dict[str, List[Interval]]:
    """Each TPU plane's ``XLA Ops`` events as (op path, start_ns,
    end_ns), read from the ``.xplane.pb`` file at ``path``; "" for an
    op whose metadata names none."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for plane in _sub(buf, 1):
        name = next((_text(v) for v in _sub(plane, 2)), "")
        if name.startswith("/device:TPU:"):
            out[name] = _plane_paths(plane)
    return out
