"""Parent's start to the worker's main: the control plane's make (a no-op when built), the lighthouse, the process spawn."""


def read(run):
    return run["setup"].get("launch")
