"""Importing jax and the whole torchft_tpu package, as every trainer does."""


def read(run):
    return run["setup"].get("import")
