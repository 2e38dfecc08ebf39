"""Programs that went to the compiler during set-up (persistent compile
cache misses, counted by jax.monitoring).  0 once the cache is warm."""


def read(run):
    return run.get("setup_compiles")
