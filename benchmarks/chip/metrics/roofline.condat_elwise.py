"""Share of the roofline reached by the ``condat_elwise``-family kernels in the
traced window, percent (see roofline.py and kernels/condat_elwise.py)."""
from roofline import share


def read(reading):
    return share(reading, "condat_elwise")
