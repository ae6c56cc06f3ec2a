"""One module per kind of traffic. A driver has three functions, each
taking the run's context (`run.py` Ctx):

    setup(ctx)            build the system from the seed, drive the probe
                          steps, warm up every shape the window uses;
                          returns the driver's state
    window(ctx, state)    the measured window; returns a dict with
                          `attempted`, `failed`, the cell's end-to-end
                          readings under `metrics`, and `steps` or
                          `batches` for the per-layer readers
    check(ctx, state, out, checks)   everything `correct` is decided by
    close(ctx, state)     stop what setup started
"""
