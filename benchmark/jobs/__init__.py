"""Measured loops, one per job kind: ``setup(cell, seed, say)`` does
everything before the window, ``measure(session, seconds=, steps=)`` is the
loop (run once untraced, and once more under the profiler in a traced run)."""
