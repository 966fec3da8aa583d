"""The decode engine's own host work a tick: its thread's seconds in
``mxtpu:serve.*`` spans that are not waits (no ``.wait_`` in the name) over
the number of ``mxtpu:serve.tick.dispatch`` spans, in milliseconds."""
from chipbench import program_spans


def read(obs):
    return program_spans.metric(obs, "engine_host_ms_per_tick")
