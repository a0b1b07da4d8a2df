"""Kernel backends of the ZO core (DESIGN.md §2): the fused axpy in
four interchangeable backends (ops.py dispatch, ref.py jnp oracle,
zo_axpy.py Pallas kernel) plus the Pallas flash-attention kernel.
"""


def default_interpret() -> bool:
    """Pallas interpret mode follows the platform: compiled Mosaic
    kernels on a TPU, the Pallas interpreter everywhere else."""
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> :func:`default_interpret`; an explicit bool stands
    (tests force the interpreter, compile rehearsals force Mosaic)."""
    return default_interpret() if interpret is None else bool(interpret)
