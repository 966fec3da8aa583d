"""Device time of the operations traced under the scope ``gdn`` (the Gated
DeltaNet mixers: projections, conv, the chunked delta rule, gated norm),
forward and backward, over device busy time."""
from chipbench import scope_time


def read(obs):
    return scope_time.share(obs, ("gdn",))
