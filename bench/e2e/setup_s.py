"""Set-up: process start to the window's opening (model and weights,
engine, warm-up of every shape the traffic uses, first admissions)."""


def read(ctx):
    return ctx.setup_s
