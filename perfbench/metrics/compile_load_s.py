"""compile (JAX persistent cache): compile or cache-load seconds during
set-up, from jax.monitoring's backend_compile_duration events."""


def read(ctx):
    return ctx["compile"]["compile_s"]
