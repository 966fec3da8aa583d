"""Host clock from building the engine (its traces) to the end of
eng.warmup() (compile or cache load of every program)."""


def read(obs):
    return obs["spans"].get("warmup_s")
