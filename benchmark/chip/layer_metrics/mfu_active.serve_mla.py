"""The window's model flops (``chipbench/axk1_cost.py``: every matrix a token
multiplies outside the routed experts, the head's slice included; the
token-expert pairs computed here, from the program's counter; causal latent
attention at the model's own key and value widths, by the live lengths; prompt
tokens and generated tokens both) over the window's seconds and the device's
bf16 peak: an end-to-end utilisation, the share of the whole serving step's
peak. None where the program keeps no such counters or the task noted no
run."""
from chipbench import axk1_cost as cost, peaks


def read(obs):
    run, window = cost.last_run(), cost.stats_window(obs)
    if not run or not window or "moe_pairs_here" not in window[1]:
        return None
    first, last = window
    delta = {k: last[k] - first[k] for k in (
        "tokens", "prompt_tokens", "prefills", "ticks", "moe_pairs_here")}
    live, pages = cost.window_means(obs, last)
    # the live slots' lengths, a tick: the live pages' positions less half
    # a page a live slot (its last page is half full on average)
    attended = delta["ticks"] * max(
        0.0, last["page_tokens"] * (pages - 0.5 * live))
    flops = cost.window_flops(
        run["cfg"], delta["tokens"] + delta["prompt_tokens"],
        delta["moe_pairs_here"], delta["prompt_tokens"], delta["prefills"],
        attended)
    peak = peaks.peaks(obs["counters"]["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / obs["spans"]["window_s"] / peak
