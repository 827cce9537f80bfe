"""Process start to the first measured request or step, by the host's clock."""


def read(ctx):
    return ctx["setup_s"]
