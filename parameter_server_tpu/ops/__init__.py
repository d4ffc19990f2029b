"""Device ops: Pallas TPU kernels with identical-math XLA references."""

import jax


def use_pallas() -> bool:
    """True when the default backend is a TPU — the one test every
    kernel dispatch in this package reads. Off the TPU each op takes its
    XLA reference path; Pallas interpret mode is never implied, only
    requested through an op's explicit ``interpret=True`` (tests)."""
    return jax.default_backend() == "tpu"
