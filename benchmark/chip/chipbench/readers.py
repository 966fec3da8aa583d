"""What several per-layer metric readers share. A reader file stays one
metric's own; the arithmetic two of them have in common lives here."""


def idle_share_percent(obs):
    """1 - union of the device's operation intervals over the traced
    window, in percent."""
    trace = obs.get("trace")
    if not trace or trace["idle_share"] is None:
        return None
    return 100.0 * trace["idle_share"]


def peak_hbm_gib(obs):
    """memory_stats()["peak_bytes_in_use"] after the window, in GiB."""
    peak = obs["counters"].get("peak_bytes_in_use")
    return peak / 2**30 if peak else None


def program(obs, by):
    """(key, program) of the traced program with the largest ``by``:
    "total_s" finds the training step, "count" the decode tick. Programs
    under 1% of the device's busy time do not compete (each dispatch also
    runs two tiny programs that split the random key). None without a
    trace."""
    trace = obs.get("trace")
    if not trace:
        return None
    real = {k: p for k, p in trace["programs"].items()
            if p["total_s"] >= 0.01 * trace["busy_s"]}
    if not real:
        return None
    key = max(real, key=lambda k: real[k][by])
    return key, real[key]
