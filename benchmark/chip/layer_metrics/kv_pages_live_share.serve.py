"""Mean of kv_pages_live / kv_pages, sampled by the benchmark once a second
inside the window."""
import statistics


def read(obs):
    c = obs["counters"]
    if not c.get("kv_pages_live") or not c.get("kv_pages"):
        return None
    return 100.0 * statistics.fmean(c["kv_pages_live"]) / c["kv_pages"]
