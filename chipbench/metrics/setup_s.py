"""Process start to the first instant of the window."""


def read(ctx):
    return ctx["setup_s"]
