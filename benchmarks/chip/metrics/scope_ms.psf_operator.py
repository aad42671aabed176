"""Device ms per solver iteration of the traced window's leaf ops whose
innermost program scope is ``psf_operator``, averaged over the chips
(see scopes.scope_ms)."""
from scopes import scope_ms


def read(reading):
    return scope_ms(reading.trace, "psf_operator", reading.iters)
