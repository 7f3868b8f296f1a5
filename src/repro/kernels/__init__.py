# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Pallas kernels. Each ``ops.py`` wrapper compiles its kernel for the
TPU when JAX runs on one and runs it in the Pallas interpreter on every
other backend; ``ref.py`` holds the pure-jnp oracle tests compare with."""
import jax


def interpret_mode() -> bool:
    """True where the kernels run interpreted: every backend but a TPU."""
    return jax.default_backend() != "tpu"
