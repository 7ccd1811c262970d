"""Per-layer metric readers: ``reduce(spec, ev)`` -> a number, or None where
there is nothing to read (the harness then leaves the metric out).

``spec`` is the metric's ``layer_metrics/<name>.json``; ``ev`` is the run's
evidence (``run.py:Evidence``): the trace and the step's parsed HLO in a
traced run, the program's spans and the step count of the untraced window,
the compile counters of set-up, the cell and the chip's peaks.
"""
