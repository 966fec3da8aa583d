"""Device time of the prefill programs over device busy time, from the
trace: every program of the window but the decode tick (the one that ran
most often)."""
from chipbench import readers


def read(obs):
    tick = readers.program(obs, "count")
    if tick is None:
        return None
    trace = obs["trace"]
    others = sum(p["total_s"] for key, p in trace["programs"].items()
                 if key != tick[0])
    return 100.0 * others / trace["busy_s"]
