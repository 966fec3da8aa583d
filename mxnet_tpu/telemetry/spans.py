"""The one span primitive: a named phase of host work on the profiler's clock.

``span(name, timer=None, **attrs)`` always enters
``jax.profiler.TraceAnnotation("mxtpu:" + name, **attrs)``: under a profiler
session (``jax.profiler.start_trace``, or ``benchmark/chip/run.py --trace
1``) the event lands on the host plane of the same ``.xplane.pb`` as the
device's operations, on one clock, on the line of the thread that ran it.
It is NOT gated by ``telemetry.ON``: with no session a span costs about two
microseconds (the annotation itself half of one). When ``telemetry.ON`` the duration also goes to
``REGISTRY.timer(timer or name)`` and the event log, so ``step_report()``
and ``export_chrome_trace`` show the same phases.

The rule for the two hot host loops (the decode engine's thread and the
compiled train step's caller): spans are FLAT LEAVES that tile the thread's
time. No span encloses another, so a span's duration is its self time and a
device idle gap splits exactly over the spans it overlaps. A name that
contains ``.wait_`` means "this thread is blocked on the device or on an
empty queue"; every other span is host work.

``SPANS`` is the inventory: add a site by adding its name here (a test holds
every name the two loops emit to it; nothing checks on the hot path).
Attribute values must not hold a comma: the profiler packs them into the
event as ``name#k=v,k=v#``.
"""
import time

from jax.profiler import TraceAnnotation

from .. import telemetry as _tm

__all__ = ["span", "SPANS", "PREFIX"]

PREFIX = "mxtpu:"

SPANS = {
    # the decode engine's scheduler thread (serve/decode/engine.py)
    "serve.gather": "draining the request queue while slots are live "
                    "(n = requests taken)",
    "serve.wait_queue": "blocked on the empty request queue with nothing "
                        "live, plus the idle-coalesce window (n)",
    "serve.expire": "dropping requests and slots past their deadline",
    "serve.admit.prepare": "radix prefix match and page allocation for one "
                           "prefill group (n, starved)",
    "serve.prefill.host": "building tokens/valid/start/table and the "
                          "slots' page-table rows (batch, length, rids, "
                          "queue_wait_ms_max)",
    "serve.prefill.stage": "device_put of the prefill's tokens, valid, "
                           "start, slot ids and table: host state into "
                           "operands (batch, length)",
    "serve.prefill.dispatch": "the prefill program's call alone: site, "
                              "admission check, fault point, the operand "
                              "list with the parameter tail, the "
                              "executable until it returns its output "
                              "arrays (batch, length, operands = the "
                              "list's length)",
    "serve.prefill.account": "rebinding the cache to the program's "
                             "outputs, with speculate_k == 1 the place "
                             "program that hands the first tokens to the "
                             "next tick on the device, and the slots' "
                             "bookkeeping: lengths, radix insert, "
                             "one-token ends (batch)",
    "serve.wait_prefill": "reading a prefill's first tokens back from the "
                          "device: with speculate_k == 1 AFTER the tick "
                          "that follows it was dispatched",
    "serve.prefill.commit": "first-token emit (the clients' on_token runs "
                            "here) and finishing a one-token request "
                            "(tokens)",
    "serve.tick.grow": "page-table growth over the live slots (live, "
                       "starved)",
    "serve.tick.draft": "draft proposal and the token operand, only "
                        "when speculate_k > 1",
    "serve.tick.stage": "the host copies of lengths and table and their "
                        "device_puts: host state into operands; with "
                        "speculate_k == 1 the token operand is the tick "
                        "before's output, still on the device (live)",
    "serve.tick.dispatch": "the tick program's call alone: site, "
                           "admission check, fault point, the operand "
                           "list with the parameter tail, the executable "
                           "until it returns its output arrays. One a "
                           "tick (live, operands = the list's length)",
    "serve.tick.account": "rebinding the cache to the program's outputs, "
                          "the flight record, and with speculate_k == 1 "
                          "every row's one token: lengths, which requests "
                          "end, their slots and pages given back (ended)",
    "serve.wait_tick": "reading a tick's tokens back from the device: "
                       "with speculate_k == 1 those of the tick BEFORE "
                       "the one just dispatched, which runs meanwhile",
    "serve.tick.commit": "emit (the clients' on_token runs here) and "
                         "finish the requests the tick ended, for the "
                         "tick just read back; with speculate_k > 1 "
                         "also accept (tokens)",
    # the caller's thread inside CompiledTrainStep (train_step.py)
    "train.assemble": "gathering the parameter, state and frozen arrays",
    "train.key": "drawing the step's PRNG key(s)",
    "train.schedule": "staged counts, learning rates, weight decays and "
                      "scalars as host arrays",
    "train.dispatch": "the compiled program's call, its first-use compile "
                      "included; feeds train_step.call / .compile",
    "train.writeback": "rebinding the program's outputs into parameters "
                       "and optimizer state",
    "train.wait_overflow": "reading the overflow flag(s) back (loss scaler "
                           "or multi-step)",
    "train.commit": "the loss scaler's update and committing the staged "
                    "counts",
    "train.wait_health": "reading the numerics monitor's health outputs "
                         "back from the device",
    "train.health": "the numerics monitor's host arithmetic",
    "train.mark": "mark_step and wrapping the loss for the caller",
    # outside the two loops; these may nest
    "cached_op.call": "one CachedOp program call (only when telemetry is "
                      "on); feeds cached_op.call / .compile",
    "profiler.<name>": "a user's profiler.scope(name): the event is "
                       "mxtpu:<name>, the timer profiler.<name>",
}


class span:  # noqa: N801 — used as a function: ``with span("x"):``
    """Context manager for one phase. ``timer`` names the registry timer
    (default: the span's name); a callable is asked at exit, which is how
    ``program_timer`` tells a compile from a call. ``after`` is the span
    that ran just before on this thread: the timer then takes ONE sample,
    of both (a program's call and the wait for its result stay one
    ``<site>.call``). ``seconds`` holds the span's own duration after
    exit."""

    __slots__ = ("name", "seconds", "_timer", "_after", "_ann", "_t0",
                 "_wall0")

    def __init__(self, name, timer=None, after=None, **attrs):
        self.name = name
        self.seconds = None
        self._timer = timer
        self._after = after
        self._ann = TraceAnnotation(PREFIX + name, **attrs)

    def note(self, **attrs):
        """Attributes known only inside the span (a count, the ids)."""
        self._ann.set_metadata(**attrs)

    def __enter__(self):
        self._wall0 = time.time()
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self.seconds = dt = time.perf_counter() - self._t0
        if _tm.ON:
            timer = self._timer or self.name
            if callable(timer):
                timer = timer()
            lead = self._after.seconds if self._after is not None else 0.0
            _tm.REGISTRY.timer(timer).record(lead + dt)
            _tm._maybe_span(timer, self._wall0 - lead, lead + dt)
        return False
