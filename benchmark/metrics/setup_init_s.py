"""init_train_state: weights and optimizer state made on the device from the seed, its program included."""


def read(run):
    return run["setup"].get("init")
