"""Seconds of set-up spent tracing, lowering, compiling or loading from
the persistent compile cache, summed from JAX's compile-duration events
(``jax.monitoring``; the events are listed in ``run.py``)."""


def read(ctx):
    return ctx.setup_compile_s
