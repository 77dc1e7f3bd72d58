"""Model FLOP utilization of the whole server update, in %: the model's
forward and backward FLOPs per update (the configuration's
``model_flops_per_update``, from shapes) times the updates completed in the
traced window, over the window's seconds times the chips times their
published bf16 peak."""


def read(ctx):
    if ctx.peaks is None or ctx.updates <= 0:
        return None
    flops = ctx.builder.model_flops_per_update(ctx.cfg, ctx.mix) * ctx.updates
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["flops_bf16"])
