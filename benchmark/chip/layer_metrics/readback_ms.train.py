"""The part of a step call's first wait (``mxtpu:train.wait_overflow`` where
the call has one, else ``mxtpu:train.wait_health``) the device did not need:
the wait's end less the later of its start and the end of the run of
``jit_mxtpu_train_step*`` it read, median over the traced step calls, in
milliseconds: the way back of the step's health outputs
(``chipbench/launch_spans.py`` states the join)."""
from chipbench import launch_spans


def read(obs):
    return launch_spans.metric(obs, "readback_ms.train")
