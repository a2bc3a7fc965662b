"""Lower + XLA seconds in set-up, from JAX's monitoring events (a program
loaded from the persistent cache counts its load)."""


def read(run):
    return run.compile_s
