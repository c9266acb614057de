"""The one spelling of "which backend is this" for the whole package.

The repo runs on two backends: XLA:CPU (tests, eight virtual devices)
and a directly attached TPU. Every site that lowers differently per
backend asks :func:`is_tpu_backend`, so the sites cannot disagree.
"""

from __future__ import annotations


def is_tpu_backend() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax

    return jax.default_backend() == "tpu"
