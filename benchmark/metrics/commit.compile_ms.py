"""State commit: JAX compile time inside the window (trace, lowering and
backend compile, from jax.monitoring), per window block."""


def read(run):
    return 1000 * run.per_block(run.compile_s)
