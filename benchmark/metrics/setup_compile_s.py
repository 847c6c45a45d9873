"""Of the other parts, not beside them: the seconds JAX spent compiling programs or loading them from its persistent cache before the window (one `backend_compile_duration` per program)."""


def read(run):
    return run["setup"].get("compile")
