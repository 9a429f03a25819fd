"""Backend capability probe shared by every Pallas kernel wrapper.

The kernels are written for the TPU's Mosaic lowering (lane rotations,
(8, 128)-tiled blocks); on every other backend (CPU foremost) they run
in interpret mode — bit-accurate kernel-body semantics, evaluated as
plain XLA ops.

``interpret=None`` on a kernel entry point means "resolve from the
backend": compiled on a TPU, interpret elsewhere.  Passing an explicit
bool is an opt-out in either direction (``interpret=True`` forces
interpretation on TPU for debugging; ``interpret=False`` on CPU fails
loudly rather than silently interpret).
"""
from __future__ import annotations

import functools

import jax

_COMPILED_BACKENDS = ("tpu",)


@functools.cache
def supports_compiled_pallas(backend: str | None = None) -> bool:
    """Does this backend have a compiled (non-interpret) Pallas lowering?"""
    return (backend or jax.default_backend()) in _COMPILED_BACKENDS


def resolve_interpret(interpret: bool | None) -> bool:
    """Map the tri-state ``interpret`` kwarg to a concrete mode."""
    if interpret is None:
        return not supports_compiled_pallas()
    return interpret
