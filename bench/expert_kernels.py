"""The held experts' grouped-matmul kernels in a device trace: their device
time, and their share of the roofline of the work a configuration's
``moe_work`` states from shapes (the balanced load: operations and bytes of
the nine products per expert layer, recomputation not counted).

Events are matched by the names a chip trace gives the kernels, those of
their jitted wrappers in ``repro/kernels/moe_gmm.py``: ``moe_gmm_kernel``
forward and for the input's gradient, ``moe_tgmm_kernel`` for the
weights'. Inside a rematerialized step XLA names them ``moe_gmm_kernel.N``;
called under a bare VJP the names gain prefixes
(``transpose_jvp_jit_moe_gmm_kernel___.2``), so a name need only contain
one. A program without them (before the dropless expert layer) has no such
events: the readers then return None.
"""

from __future__ import annotations

NAMES = ("moe_gmm_kernel", "moe_tgmm_kernel")


def device_ns(ctx) -> float:
    """The kernels' device time in the traced window, summed over the
    chips."""
    from bench import tracing
    return sum(tracing.summed_ns(
        [e for e in evs if any(n in e.name for n in NAMES)], ctx.window)
        for evs in ctx.trace.devices.values())


def roofline_share(ctx):
    """Least time of ``moe_work`` on one chip over the kernels' time per
    update, in %; None where the trace holds none of them or the
    configuration states no such work."""
    from bench import kernels
    measured = 1e-9 * device_ns(ctx)
    work = getattr(ctx.builder, "moe_work", None)
    if measured <= 0 or ctx.peaks is None or work is None:
        return None
    least, _ = kernels.least_seconds(work(ctx.cfg, ctx.mix), ctx.peaks)
    return 100.0 * least * ctx.updates / measured
