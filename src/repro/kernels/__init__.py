"""Pallas TPU kernels for the ASDR hot paths (see README.md).

The one place that decides whether a ``pallas_call`` runs compiled or in
interpret mode: interpret exactly when JAX's default backend is the CPU.
Every ``pallas_call`` takes ``interpret=resolve_interpret()`` at trace time
and no entry point can override it, so a kernel on a TPU can never quietly
fall back to the interpreter.
"""
import jax


def on_cpu() -> bool:
    """True when JAX's default backend is the host CPU."""
    return jax.default_backend() == "cpu"


def resolve_interpret() -> bool:
    """Interpret mode exactly on the CPU."""
    return on_cpu()
