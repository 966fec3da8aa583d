"""Host clock from calling the compiled step to its return, before any wait:
the median over the window's steps, in milliseconds."""
import statistics


def read(obs):
    times = obs["spans"].get("host_dispatch_s")
    return statistics.median(times) * 1e3 if times else None
