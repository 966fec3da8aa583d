"""The bytes one decode tick MUST move (``chipbench/axk1_cost.py``: the
weights outside the routed experts once, the head's slice with them; the held
experts that got a token, from the program's counter; the live latent rows,
read once a layer) at the table's HBM rate, over the tick program's device
time (``tick_device_ms.serve``): how near the tick runs to the floor its bytes
set. Only what cannot be avoided is counted, so it cannot pass 100. None
without a trace, the counters or the task's note."""
from chipbench import axk1_cost as cost, peaks, readers


def read(obs):
    run, window = cost.last_run(), cost.stats_window(obs)
    tick = readers.program(obs, "count")
    if not run or not window or not tick \
            or "moe_experts_touched" not in window[1]:
        return None
    first, last = window
    ticks = last["ticks"] - first["ticks"]
    touched = (last["moe_experts_touched"]
               - first["moe_experts_touched"]) / ticks
    _, pages = cost.window_means(obs, last)
    floor_s = cost.tick_bytes(run["cfg"], run["itemsize"], touched, pages,
                              last["page_tokens"]) \
        / peaks.peaks(obs["counters"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / tick[1]["median_s"]
