"""Share of the traced window in which no operation ran on the device, in
%: 1 minus the union of the device-op intervals over the window, averaged
over the chips used."""


def read(ctx):
    from bench import tracing
    shares = [tracing.idle_share(evs, ctx.window)
              for evs in ctx.trace.devices.values()]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
