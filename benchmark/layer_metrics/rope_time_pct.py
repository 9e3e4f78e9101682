"""ops: share of the device's busy time under ``rope``, the rotation of q and k
by position between the projections and the kernels (the rotary kernel's
calls on q and k, whichever scheme a layer has), every pass
(lib/scope_names.py). Time and no roofline: the kernel's operands stay in
VMEM."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('rope',))
