"""Device self time of the ops under the ``fl_downlink_codec`` named scope
(the downlink codec on the cut gradient, in the backward pass: top-k and
scalar quantization) per update, in ms, summed over the chips."""


def read(ctx):
    from bench import program_trace
    return program_trace.scope_ms(ctx, __file__, "fl_downlink_codec")
