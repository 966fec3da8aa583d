"""Held experts that got at least one live token, over held experts, mean
over the window's ticks and routed layers (``n_routed`` of the cell's task's
cost module, ``chipbench/task_cost.py``): how much of the experts' bytes a
tick has to stream. From the program's counter ``moe_experts_touched``
(``eng.stats()``), differenced over the window. None where the program
keeps none or no task noted a run."""
from chipbench import task_cost


def read(obs):
    cost, run = task_cost.noted()
    window = cost.stats_window(obs) if run else None
    if not window or "moe_experts_touched" not in window[1]:
        return None
    first, last = window
    lo, hi = run["experts_held"]
    slots = (last["ticks"] - first["ticks"]) \
        * cost.n_routed(run["cfg"]) * (hi - lo)
    return 100.0 * (last["moe_experts_touched"]
                    - first["moe_experts_touched"]) / slots
