"""Device self time of the ops under the ``fl_uplink_codec`` named scope (the
uplink cut codec: PQ with its Lloyd iterations and encode, all of the
codec's XLA ops and Pallas kernels, and the lambda-corrected VJP) per
update, in ms, summed over the chips."""


def read(ctx):
    from bench import program_trace
    return program_trace.scope_ms(ctx, __file__, "fl_uplink_codec")
