"""Device time of the flash attention kernels per update, in ms, summed over
the chips used; None where the trace holds none of them.

The kernels are matched by the names a chip trace gives them, those of
their jitted wrappers in ``repro/kernels/flash_attention.py``: the forward
``flash_attention_fwd``, the backward's ``flash_attention_dq`` and
``flash_attention_dkv``. Inside a rematerialized step XLA numbers them
(``flash_attention_fwd.3``) and under a bare VJP prefixes them, so a name
need only contain one. A program that runs attention without them (the
row-block scan) has no such events."""

NAMES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")


def read(ctx):
    from bench import tracing
    total = sum(tracing.summed_ns(
        [e for e in evs if any(n in e.name for n in NAMES)], ctx.window)
        for evs in ctx.trace.devices.values())
    if total <= 0 or ctx.updates <= 0:
        return None
    return 1e-6 * total / ctx.updates
