"""Backend compiles (JAX's ``backend_compile_duration`` events) that ended
inside the window; every shape is warmed up before it, so this should be
0."""


def read(ctx):
    return len(ctx.compiles_in_window)
