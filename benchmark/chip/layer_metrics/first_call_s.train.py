"""Host clock around the first call of the step: trace, compile or cache
load, and the first run."""


def read(obs):
    return obs["spans"].get("first_call_s")
