"""What the cut quantizer's kernels must do, from shapes, and how a trace
names them.

The operations and bytes are what the algorithm needs, never what an
implementation's blocks, padding or layout add: every point is read once,
every output written once, float32 throughout (the quantizer's stated
type), ``2·N·D·L`` operations for the distances. So the same work is read
whatever implements it. ``pq`` is a configuration's ``pq_work``: clients
quantized apart, points (subvectors) per client, their width, clusters and
Lloyd iterations per update.

Device events are matched by the names a chip trace gives the Pallas
kernels: the HLO instruction of a ``pallas_call`` is named after the kernel
wrapper (``lloyd_update_kernel.6``, ``pq_quantize_kernel.1``,
``scalar_quantize_kernel.1`` on a TPU v5e, PR 12).
"""

from __future__ import annotations

F32 = 4
INT32 = 4

NAMES = {
    "lloyd_update": ("lloyd_update_kernel.",),
    "pq_encode": ("pq_quantize_kernel.",),
    "scalar_quantize": ("scalar_quantize_kernel.",),
}
CODEC_KERNELS = tuple(n for names in NAMES.values() for n in names)


def lloyd_update(pq):
    """One Lloyd iteration per client: assign every point, accumulate its
    deviation from its centroid. Reads points and centroids, writes the
    deviation sums and counts."""
    n, d, l = pq["points"], pq["dim"], pq["clusters"]
    calls = pq["clients"] * pq["iters"]
    return {"flops": calls * 2.0 * n * d * l,
            "bytes": calls * float(F32 * (n * d + 2 * l * d + l))}


def pq_encode(pq):
    """The final encode per client: assign every point, write its
    reconstruction, residual and code."""
    n, d, l = pq["points"], pq["dim"], pq["clusters"]
    c = pq["clients"]
    return {"flops": c * 2.0 * n * d * l,
            "bytes": c * float(F32 * (3 * n * d + l * d) + INT32 * n)}


def least_seconds(work, peaks):
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, and which of the two it is."""
    t_ops = work["flops"] / peaks["flops_bf16"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")


def roofline_share(ctx, kernel):
    """A kernel's least time on one chip over its device time summed over
    the chips used, in %: the work of an update is split between the chips
    (a mesh places whole clients on each), so the sum is the time the whole
    work took. None where the trace holds none of its events."""
    from bench import tracing
    needles = NAMES[kernel]
    measured = 1e-9 * sum(
        tracing.summed_ns(tracing.matching(evs, needles), ctx.window)
        for evs in ctx.trace.devices.values())
    if measured <= 0 or ctx.peaks is None:
        return None
    work = globals()[kernel](ctx.builder.pq_work(ctx.cfg, ctx.mix))
    least, _ = least_seconds(work, ctx.peaks)
    return 100.0 * least * ctx.updates / measured
