"""JIT cache: backend compiles (persistent-cache reads included) inside
the measured window, from the benchmark's own ``jax.monitoring``
listener.  Every shape the cell uses is warmed up in set-up, so this
should read 0."""


def read(b):
    return b.get("window_compiles")
