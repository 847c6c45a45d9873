"""jax.devices(): loading the TPU library and taking the chip."""


def read(run):
    return run["setup"].get("acquire")
