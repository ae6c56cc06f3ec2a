"""One module per KIND of per-layer reading. `read(env, args)` returns the
value or None (nothing to read: the metric is left out of the line).

env: ctx (run.py Ctx), res (the window's dict), obs0/obs1 (the program's
metric registry before and after the window, by name), trace (the
reduction of the profiler trace, trace_reduce.reduce), device.
"""
