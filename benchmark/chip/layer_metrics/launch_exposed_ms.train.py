"""How long the lead device stood idle before a run of the step program
although the caller's thread had already begun the call: the run's start less
the later of its ``mxtpu:train.dispatch`` span's start and the end of the
device's last operation before it, median over the traced runs of
``jit_mxtpu_train_step*``, in milliseconds (``chipbench/launch_spans.py``
states the join)."""
from chipbench import launch_spans


def read(obs):
    return launch_spans.metric(obs, "launch_exposed_ms.train")
