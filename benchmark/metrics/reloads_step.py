"""Programs per step that the loop traces, lowers and fetches from the
compile cache AGAIN (a call the program under test never jits: every
invocation is a new function to JAX). None were compiled: the benchmark
keeps every program in the persistent cache, however small; under JAX's
default one-second threshold these would be recompiled every step."""


def read(run):
    return run["programs_reloaded"] / len(run["records"])
