"""The cost module of the serving task that noted its run in this process.

A serving task's ``<task>_cost.py`` counts its model's operations and bytes
and keeps what the task built (``note_run``); its ``note_run`` hands the
module here. The readers of the serving metrics that every routed
configuration shares (``mfu_active.serve``, ``tick_hbm_share.serve``,
``held_experts_touched_share.serve``) take their counts from it, so a new
configuration brings a cost module with the same functions and lists the
shared metric: no copy of the metric.

What a serving cost module offers them: ``last_run()``, ``stats_window``,
``window_means``, ``window_flops``, ``tick_bytes`` (called by keyword:
``experts_touched``, ``live_slots``, ``pages_live``, ``page_tokens``) and
``n_routed``.
"""

_NOTED = []


def note(module):
    """Called by a cost module's ``note_run``: the newest note is the run's
    (one process runs one cell)."""
    _NOTED[:] = [module]


def noted():
    """``(module, module.last_run())`` of the newest note; ``(None, None)``
    where no task noted a run."""
    if not _NOTED:
        return None, None
    return _NOTED[0], _NOTED[0].last_run()
