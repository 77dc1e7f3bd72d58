"""The Lloyd-update Pallas kernel's share of its roofline, in %: the least
time the algorithm's bytes and operations need (``bench/kernels.py``) over
the kernel's summed device time in the traced window."""


def read(ctx):
    from bench import kernels
    return kernels.roofline_share(ctx, "lloyd_update")
