"""Generator's busy share, from its own clock."""


def read(obs, params):
    gen = obs["gen"]
    ran = gen["ran_s"]
    if ran <= 0:
        return None
    return 100.0 * gen["busy_s"] / ran
