"""The bytes one decode tick MUST move, counted by the cost module of the
cell's task (``chipbench/task_cost.py``: the weights outside the routed
experts once, the head's slice with them; the held experts that got a token,
from the program's counter; what the live slots hold, state or pages, read
once) at the table's HBM rate, over the tick program's device time
(``tick_device_ms.serve``): how near the tick runs to the floor its bytes
set. Only what cannot be avoided is counted, so it cannot pass 100. None
without a trace, the counters or a task's note."""
from chipbench import peaks, readers, task_cost


def read(obs):
    cost, run = task_cost.noted()
    window = cost.stats_window(obs) if run else None
    tick = readers.program(obs, "count")
    if not window or not tick or "moe_experts_touched" not in window[1]:
        return None
    first, last = window
    ticks = last["ticks"] - first["ticks"]
    touched = (last["moe_experts_touched"]
               - first["moe_experts_touched"]) / ticks
    live, pages = cost.window_means(obs, last)
    floor_s = cost.tick_bytes(
        run["cfg"], run["itemsize"], experts_touched=touched,
        live_slots=live, pages_live=pages, page_tokens=last["page_tokens"]) \
        / peaks.peaks(obs["counters"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / tick[1]["median_s"]
