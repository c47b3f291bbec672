"""Seconds of JAX's backend-compile events during set-up.  A persistent
compile-cache hit is such an event too, spanning the cache read, so on a
warm checkout this is the time to load the programs."""


def read(run):
    return run.setup_compile_s if run.setup_compiles else None
