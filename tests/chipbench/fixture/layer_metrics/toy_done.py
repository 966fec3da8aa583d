"""A per-layer metric a later PR might add, as a reader of its own."""


def read(obs):
    return obs["counters"].get("done")
