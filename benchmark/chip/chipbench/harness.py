"""Look a cell up in ``BENCHMARK.json``, find its files by name, run its
runner and print the result line. Knows no model, cell or metric by name.

Files of a cell, all under the benchmark's directory:

- ``configs/<config>.json``   the configuration as it is run; its ``task``
  names ``tasks/<task>.py`` (how to build the net, its batches, its loss and
  its comparison with the plain reference);
- ``traffic/<traffic>.json``  the mix's parameters; its ``runner`` names
  ``runners/<runner>.py`` with one ``run(config, traffic, seed, seconds,
  trace, env)`` that returns the observations of one run;
- ``layer_metrics/<metric>.py``  one ``read(obs)`` per per-layer metric,
  returning a number or None (then the metric is left out of the line).
"""
import importlib.util
import json
import os
import time


class BenchError(Exception):
    """The run cannot produce a result line."""


def say(msg):
    """A line for the reader, before the result line."""
    print(msg, flush=True)


def load_json(bench_dir, kind, name):
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {path}")
    with open(path) as fh:
        return json.load(fh)


def load_module(bench_dir, kind, name):
    """``<bench_dir>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} module {path}")
    mod_name = "chipbench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest, workload):
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json (has: "
                     f"{[c['name'] for c in manifest['workloads']]})")


def metrics_of(manifest, group, workload):
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def resolve(manifest, workload, bench_dir):
    """Everything the cell names, loaded; raises where a file is missing."""
    cell = find_cell(manifest, workload)
    config = load_json(bench_dir, "configs", cell["config"])
    traffic = load_json(bench_dir, "traffic", cell["traffic"])
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "runner": load_module(bench_dir, "runners", traffic["runner"]),
        "readers": {m["name"]: load_module(bench_dir, "layer_metrics",
                                           m["name"])
                    for m in metrics_of(manifest, "per_layer", workload)},
    }


class CompileLog:
    """Counts what jax compiled or fetched from the persistent cache."""

    def __init__(self):
        from jax import monitoring

        self.n = self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.n, self.hits, self.seconds)

    def since(self, mark):
        """(programs built or loaded, of them from the cache, seconds)."""
        n, hits, s = mark
        return (self.n - n, self.hits - hits, self.seconds - s)


class Env:
    """What the harness hands a runner besides the cell's own data."""

    def __init__(self, bench_dir, work_dir, chips, t_start, compile_log):
        self.bench_dir = bench_dir
        self.work_dir = work_dir       # scratch inside the checkout
        self.chips = chips
        self.t_start = t_start         # perf_counter() at process start
        self.compile_log = compile_log
        self.say = say

    def load_task(self, name):
        return load_module(self.bench_dir, "tasks", name)


def device_info(chips):
    """The devices as jax reports them; refuses anything but enough TPUs of
    a kind the peaks table knows."""
    import jax

    from . import peaks

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if info["platform"] != "tpu":
        raise BenchError(f"no TPU: jax found {info}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, jax has "
                         f"{len(devices)}")
    try:
        peaks.peaks(info["kind"])
    except peaks.UnknownDevice as e:
        raise BenchError(str(e)) from None
    info["count"] = chips
    return info, devices[:chips]


def memory_peak_bytes(devices):
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def build_result(manifest, workload, obs, readers, trace, device):
    """The result object from a runner's observations. ``--trace 0``: the
    cell's end-to-end metrics; ``--trace 1``: its per-layer metrics."""
    metrics = {}
    summary = obs.get("trace")
    if trace and not (summary and summary["busy_s"] > 0):
        raise BenchError("the traced run saw no operation on the device")
    group = "per_layer" if trace else "end_to_end"
    for m in metrics_of(manifest, group, workload):
        if trace:
            value = readers[m["name"]].read(obs)
            if value is None:
                continue
        else:
            value = obs["end_to_end"].get(m["name"])
            if value is None:
                raise BenchError(f"the runner gave no {m['name']}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=int(obs["memory_peak_bytes"]))
    result = {"correct": bool(obs["correct"]),
              "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"][:10],
                               "idle_gaps": summary["idle_gaps"][:10]}
    return result


def main(args, bench_dir, repo_root, t_start):
    """Run one cell; returns the process's exit code."""
    with open(os.path.join(repo_root, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    found = resolve(manifest, args.workload, bench_dir)
    cell = found["cell"]
    try:
        import mxnet_tpu as mx
    except ImportError as e:
        raise BenchError(f"the system under test is not here: {e}") from None
    device, devices = device_info(int(cell["chips"]))
    if os.environ.get("MXTPU_PALLAS_INTERPRET", "") == "1":
        raise BenchError("MXTPU_PALLAS_INTERPRET=1: kernels would be "
                         "interpreted")
    import jax

    cache_dir = mx.context.enable_compilation_cache()
    files = [os.path.join(cache_dir, f) for f in os.listdir(cache_dir)]
    say(f"jax {jax.__version__}, device {device['kind']} x{device['count']} "
        f"({device['platform']}); compile cache {cache_dir}: {len(files)} "
        f"files, {sum(map(os.path.getsize, files)) / 2**20:.1f} MiB at "
        f"start, size cap {jax.config.jax_compilation_cache_max_size}")
    work_dir = os.path.join(bench_dir, ".work")
    os.makedirs(work_dir, exist_ok=True)
    env = Env(bench_dir, work_dir, int(cell["chips"]), t_start, CompileLog())
    obs = found["runner"].run(found["config"], found["traffic"], args.seed,
                              args.seconds, bool(args.trace), env)
    obs["memory_peak_bytes"] = memory_peak_bytes(devices)
    for name, ok in sorted(obs.get("checks", {}).items()):
        say(f"check {name}: {'ok' if ok is True else ok}")
    result = build_result(manifest, args.workload, obs, found["readers"],
                          bool(args.trace), device)
    say(f"whole run {time.perf_counter() - t_start:.1f}s")
    print(json.dumps(result), flush=True)
    return 0
