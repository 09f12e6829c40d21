"""Seconds JAX spends tracing, lowering and compiling, or loading from the
persistent cache, during set-up (JAX's /jax/core/compile/* events); moves
setup_s."""


def read(run):
    return run.compile_s
