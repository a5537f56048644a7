"""Seconds of `JaxTrainer.fit()` outside the training loop it ran."""


def read(ctx):
    return ctx["fit_s"] - ctx["loop_s"]
