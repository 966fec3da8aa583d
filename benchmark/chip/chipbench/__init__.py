"""The chip benchmark's yardstick: everything a later PR may not change.

``run.py`` (one directory up) is the entry point. This package holds the
pieces no cell owns: the manifest lookup and result line (``harness``), the
traffic generator (``traffic``), the reduction from a profiler trace to
metrics (``trace_reduce``), the plain float32 references (``reference``) and
the table of peaks (``peaks``). What belongs to ONE configuration, traffic
mix, runner, task or per-layer metric sits in a file of its own beside this
package and is found by the name ``BENCHMARK.json`` gives it.
"""
