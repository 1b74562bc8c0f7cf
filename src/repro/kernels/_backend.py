"""One home for the kernels' backend policy (imported by every kernel module;
ops.py reuses it too — this module must stay import-cycle-free, so it imports
nothing from repro)."""
from __future__ import annotations

import jax

# Scoped VMEM a kernel may claim: v5e has 128 MiB per core, and the
# compiler's default scope is 16 MiB.
VMEM_LIMIT = 64 << 20


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(flag: bool | None) -> bool:
    """``interpret=None`` (every kernel entry point's default) resolves
    backend-aware: Mosaic on a real TPU, the Pallas interpreter elsewhere.
    An explicit bool always wins (tests force the interpreter; a TPU run can
    force it for debugging)."""
    if flag is None:
        return not on_tpu()
    return bool(flag)
