"""A serving metric that one appended cell brings and lists alone; it finds
nothing to read and returns nothing."""


def read(obs):
    return obs["counters"].get("appended_probe")
