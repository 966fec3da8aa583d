"""Host work inside one call of the compiled step: the caller thread's
``mxtpu:train.*`` spans that are not waits (no ``.wait_`` in the name),
summed per step call, median over the traced steps, in milliseconds."""
from chipbench import program_spans


def read(obs):
    return program_spans.metric(obs, "step_host_ms")
