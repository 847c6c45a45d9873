"""The warm-up steps (the loop's programs compile or load inside the first), less the first quorum wait."""


def read(run):
    return run["setup"].get("warm")
