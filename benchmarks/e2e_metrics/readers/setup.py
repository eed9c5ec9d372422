"""Process start until the measured window opens."""


def read(obs, params):
    return obs["setup_seconds"]
