"""The part of a ``mxtpu:serve.wait_tick`` span the device did not need: the
wait's end less the later of its start and the end of the run of
``jit_mxtpu_serve_decode*`` it read, median over the traced waits, in
milliseconds: the way back of a tick's tokens (``chipbench/launch_spans.py``
states the join)."""
from chipbench import launch_spans


def read(obs):
    return launch_spans.metric(obs, "readback_ms.serve")
