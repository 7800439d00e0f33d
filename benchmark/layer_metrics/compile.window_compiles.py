"""XLA compilations inside the window (JAX's `backend_compile_duration`
events). Expected 0: a value above it means a shape was not warmed up, and the
window's times hold a compile."""


def read(ctx):
    return ctx["window_compiles"]
