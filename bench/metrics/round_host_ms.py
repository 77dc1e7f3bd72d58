"""Host time inside ``FederatedTrainer.round`` per update, in ms: the
benchmark's host clock around each call (cohort sampling, one batch fetch
and ``fold_in`` per client, the concatenation, the step's dispatch)."""


def read(ctx):
    if ctx.updates <= 0:
        return None
    return 1e3 * ctx.host_round_s / ctx.updates
