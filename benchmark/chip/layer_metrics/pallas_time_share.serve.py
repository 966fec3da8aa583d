"""Device time of the Pallas kernels (tpu_custom_call) over device busy
time, from the trace."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["custom_call_s"] / trace["busy_s"]
