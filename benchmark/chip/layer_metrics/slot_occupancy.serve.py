"""Mean share of slots holding a live request per decode tick inside the
window: eng.stats()'s mean_slot_occupancy x ticks, read at the window's start
and end, the difference over the ticks between."""


def read(obs):
    occ = obs["counters"].get("slot_occupancy")
    return None if occ is None else 100.0 * occ
