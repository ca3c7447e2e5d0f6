"""Pallas kernels for the paper's hot spots plus their jitted ops."""

import jax


def resolve_interpret(interpret):
    """``None`` picks the mode for the backend: Mosaic-compiled on a TPU,
    Pallas interpret mode everywhere else (CPU, GPU hosts)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
