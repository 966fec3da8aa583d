"""A runner a later PR might add: new files only, found by its name."""


def run(config, traffic, seed, seconds, trace, env):
    done = config["scale"] * traffic["work"]
    return {
        "attempted": done, "failed": 0, "correct": True, "checks": {},
        "end_to_end": {"toy_ops_per_s": done / seconds, "setup_s": 0.25},
        "spans": {}, "counters": {"done": done},
        "memory_peak_bytes": 1024,
        "trace": {"busy_s": 0.5, "window_s": 1.0, "idle_share": 0.5,
                  "device_ops": [["op", 0.5]], "idle_gaps": [["wait", 0.5]]}
        if trace else None,
    }
