"""setup_compile_s: seconds set-up spent getting the cell's compiled
programs (tracing, lowering, and compiling or loading from the persistent
cache), from JAX's own compile events."""


def read(ctx):
    return ctx["setup_compile_s"]
