"""Share of the traced window, in %, in which the device is idle while
the host dispatches the step: idle time inside the program's
``executor.dispatch`` spans, averaged over the chips."""


def read(ctx):
    from bench import program_trace
    return program_trace.idle_share(ctx, __file__, 1)
