"""Device self time of the ops under the ``fl_server`` named scope (the server
half and the loss head, forward and backward) per update, in ms, summed
over the chips."""


def read(ctx):
    from bench import program_trace
    return program_trace.scope_ms(ctx, __file__, "fl_server")
