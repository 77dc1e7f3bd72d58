"""The held experts' grouped-matmul kernels' share of their roofline, in %:
the least time of the configuration's ``moe_work`` (``bench/
expert_kernels.py``, from shapes at a balanced load) over the kernels'
summed device time in the traced window."""


def read(ctx):
    from bench import expert_kernels
    return expert_kernels.roofline_share(ctx)
