"""Device ms per solver iteration of the traced window's leaf ops whose
innermost program scope is ``starlet``, averaged over the chips
(see scopes.scope_ms)."""
from scopes import scope_ms


def read(reading):
    return scope_ms(reading.trace, "starlet", reading.iters)
