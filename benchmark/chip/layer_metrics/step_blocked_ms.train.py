"""How long one call of the compiled step holds its caller for the device:
``mxtpu:train.wait_health`` + ``mxtpu:train.wait_overflow`` per step call,
median over the traced steps, in milliseconds."""
from chipbench import program_spans


def read(obs):
    return program_spans.metric(obs, "step_blocked_ms")
