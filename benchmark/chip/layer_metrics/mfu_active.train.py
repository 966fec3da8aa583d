"""The step's model flops (``chipbench/qwen3_next_cost.py``: the matrices a
token multiplies outside the routed experts, the token-expert pairs computed
here from the program's counter, causal attention, the delta rule's chunk
products) times the run's own steps a second, over the device's bf16 peak: an
end-to-end utilisation, the share of the whole step's peak. Recomputation
does not count. None where the program keeps no expert counters."""
from chipbench import peaks, qwen3_next_cost


def read(obs):
    run = qwen3_next_cost.last_run()
    try:
        from mxnet_tpu import telemetry
        report = telemetry.moe_report()
    except (ImportError, AttributeError):
        return None
    if not run or not report:
        return None
    c = obs["counters"]
    flops = qwen3_next_cost.step_flops(run["cfg"], run["rows"], run["length"],
                                       report["moe.pairs_here"])
    peak = peaks.peaks(c["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * c["tokens_per_s"] / c["tokens_per_step"] / peak
