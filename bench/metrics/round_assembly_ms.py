"""Host time of round assembly per update, in ms: the program's
``trainer.round`` spans less the ``executor.dispatch`` spans inside them
(the cohort draw, one batch fetch and ``fold_in`` per client, the
concatenation, the placement), read from the traced window."""


def read(ctx):
    from bench import program_trace
    return program_trace.host_ms(ctx, __file__, 0)
