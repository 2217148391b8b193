"""Backend compile seconds during set-up, summed from JAX's own
``/jax/core/compile/backend_compile_duration`` events."""


def read(run):
    return run.compile_s_setup
