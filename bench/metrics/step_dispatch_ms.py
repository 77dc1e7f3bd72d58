"""Host time of the step's dispatch per update, in ms: the program's
``executor.dispatch`` spans around the call of the jitted step, read from
the traced window."""


def read(ctx):
    from bench import program_trace
    return program_trace.host_ms(ctx, __file__, 1)
