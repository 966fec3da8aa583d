"""The bookkeeping behind a program's call, a tick, on the decode engine's
thread: its seconds in ``mxtpu:serve.tick.account`` + ``mxtpu:serve.prefill
.account`` (rebinding the cache, the flight record, every row's token
accounted, slots and pages given back, the place program, the radix insert)
over the number of ``mxtpu:serve.tick.dispatch`` spans, in milliseconds. None
for a program from before the dispatch spans were cut into leaves."""
from chipbench import launch_spans


def read(obs):
    return launch_spans.metric(obs, "engine_account_ms_per_tick.serve")
