"""Device time of the held experts' grouped-matmul kernels per update, in
ms, summed over the chips used; None where the trace holds none of them."""


def read(ctx):
    from bench import expert_kernels
    total = expert_kernels.device_ns(ctx)
    if total <= 0 or ctx.updates <= 0:
        return None
    return 1e-6 * total / ctx.updates
