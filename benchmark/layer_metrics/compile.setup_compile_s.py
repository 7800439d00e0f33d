"""Seconds of XLA compilation before the window (JAX's
`backend_compile_duration` events; a persistent-cache hit costs its retrieval
only). The part of `setup_s` that the compile cache decides."""


def read(ctx):
    return ctx["setup_compiles"]["seconds"]
