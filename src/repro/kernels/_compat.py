"""Helpers shared by the kernel modules: tile padding, FLOP counts, and the
one place that decides whether Pallas kernels compile or interpret."""

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter on this platform.

    Kernels compile with Mosaic on a TPU and interpret only on the CPU,
    which is the test platform.  Any other backend is an error: a quiet
    interpreter run there would hide that the kernels never reached the
    device.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; the default "
        f"JAX backend is {platform!r}")


def round_up(x: int, m: int) -> int:
    """Round x up to a multiple of m (tile padding)."""
    return (x + m - 1) // m * m


def mlp_flops(dims) -> int:
    """MAC-pair FLOPs for ONE item through an MLP with layer dims `dims` —
    the single source for the kernels' dispatcher cost hints."""
    return 2 * sum(k * n for k, n in zip(dims[:-1], dims[1:]))


__all__ = ["interpret_mode", "round_up", "mlp_flops"]
