"""Share of the roofline reached by the ``starlet2d``-family kernels in the
traced window, percent (see roofline.py and kernels/starlet2d.py)."""
from roofline import share


def read(reading):
    return share(reading, "starlet2d")
