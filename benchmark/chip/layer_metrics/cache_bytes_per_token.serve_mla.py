"""The cache's bytes over the positions it can hold, all layers together
(``eng.stats()``: ``cache_bytes`` over ``kv_pages x page_tokens``): 6912 for
six layers of one 576-wide bfloat16 row, where per-head K and V would be
245,760. It guards the latent layout against a per-head cache coming back.
None where the program's readings lack the sizes (every commit before
them)."""
from chipbench import axk1_cost as cost


def read(obs):
    window = cost.stats_window(obs)
    if not window or not all(window[1].get(k) for k in (
            "cache_bytes", "kv_pages", "page_tokens")):
        return None
    last = window[1]
    return last["cache_bytes"] / (last["kv_pages"] * last["page_tokens"])
