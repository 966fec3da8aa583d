"""Device time of the operations traced under the scopes ``router`` and
``experts`` (routing and its counters, the grouped products over the experts
held, the shared MLP or expert), in every serving program, over device busy
time; by self time, a module at a time (``chipbench/scope_time_serve.py``).
Knows no model: every routed serving cell lists it."""
from chipbench import scope_time_serve


def read(obs):
    return scope_time_serve.share(obs, ("router", "experts"))
