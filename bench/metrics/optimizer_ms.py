"""Device self time of the ops under the ``fl_optimizer`` named scope (the
optimizer's update and the parameters' add) per update, in ms, summed over
the chips."""


def read(ctx):
    from bench import program_trace
    return program_trace.scope_ms(ctx, __file__, "fl_optimizer")
