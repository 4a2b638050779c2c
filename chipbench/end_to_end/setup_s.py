"""Process start to the first timed step: imports, JAX start-up, weights,
compiles (or reads from the compile cache), the check's first steps and the
window's batches."""


def read(ctx):
    return ctx.setup_s
