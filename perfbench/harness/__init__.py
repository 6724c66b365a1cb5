"""The benchmark's own code: the yardstick later PRs may add to, not edit.

Nothing here imports ``paddle_tpu.observability``; from the program the
benchmark takes the system under test, its spans, counters and kernel names.
"""
