"""Building the Manager (its server, its store) and the first quorum, the init-sync heal from group 0 included."""


def read(run):
    return run["setup"].get("quorum")
