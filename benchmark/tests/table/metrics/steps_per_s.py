"""Test only: window steps over window seconds."""


def read(run):
    return len(run["records"]) / run["window_s"]
