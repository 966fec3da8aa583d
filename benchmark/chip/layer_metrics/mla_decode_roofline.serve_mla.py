"""The absorbed latent decode attention's share of its roofline in the tick:
the least time its work can take (``chipbench/axk1_cost.mla_decode_floor_s``:
the larger of the live latent pages' bytes, read once a layer, over the
table's HBM rate and of its flops over the bf16 peak; at 120 flops a byte the
bytes bound it on a v5e) times the traced ticks, over the device time of the
kernel ``mxtpu_mla_decode`` in the trace. None without a trace, the task's
note, or where no such kernel ran."""
from chipbench import axk1_cost as cost, peaks, readers


def read(obs):
    run, window = cost.last_run(), cost.stats_window(obs)
    tick = readers.program(obs, "count")
    if not run or not window or not tick:
        return None
    seconds = cost.kernel_seconds()
    if not seconds:
        return None
    _, pages = cost.window_means(obs, window[1])
    floor_s = cost.mla_decode_floor_s(
        run["cfg"], run["itemsize"], pages, window[1]["page_tokens"],
        peaks.peaks(obs["counters"]["device_kind"]))
    return 100.0 * floor_s * tick[1]["count"] / seconds
