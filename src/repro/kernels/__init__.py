"""Pallas kernels for the paper's hot spots (integer softmax, the fused
integer attention, paged decode), and the one rule for when they run in
the Pallas interpreter instead of being compiled."""

import jax


def resolve_interpret(interpret=None) -> bool:
    """The ``interpret`` flag for a ``pallas_call``: an explicit value wins;
    by default the kernel compiles on a TPU and runs in the interpreter on
    the CPU (how the test suite runs). Any other platform raises — a kernel
    never silently stops being a kernel on a device it was not built for."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target TPU (compiled) or CPU (interpret "
        f"mode); backend {platform!r} is neither — pass interpret= "
        f"explicitly or run with JAX_PLATFORMS=cpu")
