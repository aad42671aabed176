"""Peaks of each chip and a kernel family's share of its roofline.

A family's share is the least time the chip could take for the work
the algorithm needs, over the device time the family's kernels took in
the traced window.  The work of one execution of each kernel is
counted from the logical shapes by ``kernels/<family>.py`` (its
``KERNELS`` maps each ``pallas_call`` name to a function of the cell
that returns ``(flops, bytes)``), not from the padded tiles: the share
then shows what padding and layout cost.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Optional, Tuple

from cells import component

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> Dict[str, float]:
    """FLOP/s and bytes/s of one chip; an unknown chip is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to peaks.json with its source")
    return table[device_kind]


def family(name: str):
    return component("kernels", name)


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The roofline bound of one execution and which side binds it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def share(reading, fam: str) -> Optional[float]:
    """Percent of the roofline that ``fam``'s kernels reached in the
    traced window, over all chips; None where none of them ran."""
    from devtrace import kernel_events
    kernels = family(fam).KERNELS
    peak = peaks(reading.device_kind)
    lo, hi = reading.trace.window
    least = spent = 0.0
    for dev in reading.trace.devices:
        for kname, work in kernels.items():
            evs = kernel_events(dev, kname, lo, hi)
            if not evs:
                continue
            t, _ = least_seconds(*work(reading.cell), peak)
            least += t * len(evs)
            spent += sum(e - s for _, s, e in evs) * 1e-9
    return 100.0 * least / spent if spent > 0 else None
