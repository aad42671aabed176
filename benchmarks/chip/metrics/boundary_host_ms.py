"""Mean host time at a chunk boundary inside the traced window, ms:
from the end of one chunk's ``repro.chunk.wait`` span to the end of
the next chunk's ``repro.chunk.dispatch``, the host's own critical path
while the device has nothing queued.  None where the program emits no
chunk spans."""
import bisect

WAIT, DISPATCH = "repro.chunk.wait", "repro.chunk.dispatch"


def read(reading):
    tr = reading.trace
    lo, hi = tr.window

    def ends(name):
        return sorted(e for n, s, e in tr.host
                      if n == name and s >= lo and e <= hi)

    dispatched = ends(DISPATCH)
    gaps = []
    for w in ends(WAIT):
        i = bisect.bisect_right(dispatched, w)
        if i < len(dispatched):
            gaps.append(dispatched[i] - w)
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
