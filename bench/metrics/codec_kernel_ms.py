"""Device time of the cut codecs' Pallas kernels (Lloyd update, PQ encode,
scalar quantize) per update, in ms, summed over the chips used; None where
the trace holds none of them."""


def read(ctx):
    from bench import kernels, tracing
    total = sum(tracing.summed_ns(tracing.matching(evs, kernels.CODEC_KERNELS),
                                  ctx.window)
                for evs in ctx.trace.devices.values())
    if total <= 0 or ctx.updates <= 0:
        return None
    return 1e-6 * total / ctx.updates
