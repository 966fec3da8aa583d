"""What turning host state into operands costs the decode engine's thread a
tick: its seconds in ``mxtpu:serve.tick.stage`` + ``mxtpu:serve.prefill
.stage`` (the host copies of lengths and table, the ``device_put``s) over the
number of ``mxtpu:serve.tick.dispatch`` spans, in milliseconds. None for a
program from before the dispatch spans were cut into leaves."""
from chipbench import launch_spans


def read(obs):
    return launch_spans.metric(obs, "engine_stage_ms_per_tick.serve")
