"""Device self time of the ops under the ``fl_client`` named scope (the client
half: its forward pass and that pass's transpose) per update, in ms, summed
over the chips."""


def read(ctx):
    from bench import program_trace
    return program_trace.scope_ms(ctx, __file__, "fl_client")
