"""Device time by the program's named scopes: join a trace with the compiled
step's text.

A device event is named by its HLO instruction (``%fusion.12 = ...``); the
``jax.named_scope`` an operation was traced under is only in the compiled
text, in the instruction's ``metadata={op_name="jit(..)/grad/jvp(gdn)/.."}``
(PERF.md section 7). This module reads both: the trace where the runners
write it (as ``program_spans`` does), the text in-process through
``mxnet_tpu.train_step.compiled_modules()``. A program without that accessor
or without the scopes, as every commit before them, gives None.
"""
import os
import re

from . import program_spans, trace_reduce

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+) = .*metadata=\{[^}]*'
    r'op_name="([^"]*)"')

_READINGS = {}   # (path, mtime) -> {instruction name: seconds}: the last load


def scope_pattern(scope):
    """``gdn`` as one whole component of an op_name path, bare or wrapped by
    a transformation: ``.../gdn/...``, ``jvp(gdn)``, ``transpose(jvp(gdn))``."""
    return re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")


def instruction_scopes(hlo_text):
    """{instruction name: op_name} of every instruction that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def event_instruction(event_name):
    """``fusion.12`` from ``%fusion.12 = f32[..] fusion(..)`` or from the
    bare name."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def self_seconds(events):
    """{instruction name: seconds} of one line of ``(name, start, duration)``
    events, every moment given to the INNERMOST event that covers it. The
    ops line nests: a ``while`` event spans its iterations and the body's
    operations lie inside it (a loop in a loop twice over), so the plain sum
    of durations counts a loop's time once for the ``while`` and again for
    every level below it. Counted this way the seconds add up to the union
    of the line's intervals, the device's busy time, whatever the nesting."""
    by, stack, cursor = {}, [], 0.0    # stack: [end, instruction]

    def give(inst, upto):
        nonlocal cursor
        if upto > cursor:
            by[inst] = by.get(inst, 0.0) + (upto - cursor) / 1e9
            cursor = upto

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            end, inst = stack.pop()
            give(inst, end)
        if stack:
            give(stack[-1][1], start)
        cursor = max(cursor, start)
        stack.append([start + dur, event_instruction(name)])
    while stack:
        end, inst = stack.pop()
        give(inst, end)
    return by


def seconds_by_instruction(trace_dir=None):
    """Device seconds of each instruction, nested events taken out
    (``self_seconds``), on the busiest device of the newest trace; None
    where there is no trace or no device in it."""
    path = trace_reduce.newest_xplane(
        trace_dir or program_spans.default_trace_dir())
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _READINGS:
        devices = [self_seconds(line["events"])
                   for plane in trace_reduce.load_xplane(path)
                   if trace_reduce.DEVICE_PLANE.match(plane["name"])
                   for line in plane["lines"]
                   if line["name"] == trace_reduce.OPS_LINE]
        _READINGS.clear()
        _READINGS[key] = max(devices, key=lambda by: sum(by.values()),
                             default=None)
    return _READINGS[key]


def compiled_texts():
    """The text of every live compiled step, or None where the program has
    no such accessor."""
    try:
        from mxnet_tpu import train_step
    except ImportError:
        return None
    modules = getattr(train_step, "compiled_modules", None)
    if modules is None:
        return None
    return [c.as_text() for c in modules().values()]


def scope_seconds(scopes, trace_dir=None, texts=None):
    """Device seconds of the operations traced under any of ``scopes``;
    None without a trace, without the compiled text, or where no instruction
    of the text lies under one of them."""
    by = seconds_by_instruction(trace_dir)
    texts = compiled_texts() if texts is None else texts
    if not by or not texts:
        return None
    patterns = [scope_pattern(s) for s in scopes]
    under = set()
    for text in texts:
        for inst, op_name in instruction_scopes(text).items():
            if any(p.search(op_name) for p in patterns):
                under.add(inst)
    if not under:
        return None
    return sum(s for inst, s in by.items() if inst in under)


def share(obs, scopes):
    """What a per-layer reader returns: the scopes' device time over the
    device's busy time, in percent."""
    trace = obs.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    seconds = scope_seconds(scopes)
    if seconds is None:
        return None
    return 100.0 * seconds / trace["busy_s"]
