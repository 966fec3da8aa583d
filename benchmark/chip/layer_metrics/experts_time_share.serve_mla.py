"""``experts_time_share.serve`` for the A.X-K1 cell: device time of the
operations traced under the scopes ``router`` and ``experts`` (the sigmoid
routing and its counters, the grouped products over the experts held, the
shared expert), in every serving program, over device busy time
(``chipbench/scope_time_serve.py``). A double only because the accepted
metric's list of cells is pinned to the Granite cell by a test this PR may not
edit (``tests/chipbench/test_chipbench_granite_hybrid.py``); PERF.md section 7
asks a ``benchmark`` PR to merge them."""
from chipbench import scope_time_serve


def read(obs):
    return scope_time_serve.share(obs, ("router", "experts"))
