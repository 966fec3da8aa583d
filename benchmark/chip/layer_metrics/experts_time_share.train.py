"""Device time of the operations traced under the scopes ``router`` and
``experts`` (routing, the dropless grouped products over the experts held,
the shared expert), forward and backward, over device busy time."""
from chipbench import scope_time


def read(obs):
    return scope_time.share(obs, ("router", "experts"))
