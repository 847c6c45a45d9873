"""The reference check on one seeded sample (group 0), its programs included."""


def read(run):
    return run["setup"].get("check")
