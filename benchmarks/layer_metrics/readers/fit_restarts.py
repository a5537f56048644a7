"""Worker groups the train controller lost and started again before the
attempt that ran to its end (`FailureConfig.max_failures` allows two)."""


def read(ctx):
    return ctx.get("restarts")
