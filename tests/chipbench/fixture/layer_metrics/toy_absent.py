"""A reader that finds nothing to read returns nothing."""


def read(obs):
    return obs["counters"].get("never_counted")
