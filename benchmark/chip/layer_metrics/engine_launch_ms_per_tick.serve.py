"""The program calls alone, a tick, on the decode engine's thread: its
seconds in the NARROWED ``mxtpu:serve.tick.dispatch`` + ``mxtpu:serve.prefill
.dispatch`` (site, admission check, fault point, the operand list with the
parameter tail, the executable until it returns its output arrays) over the
number of ``mxtpu:serve.tick.dispatch`` spans, in milliseconds. None unless
the trace holds a ``mxtpu:serve.tick.stage`` span: the un-narrowed span of an
older program is never read under this name."""
from chipbench import launch_spans


def read(obs):
    return launch_spans.metric(obs, "engine_launch_ms_per_tick.serve")
