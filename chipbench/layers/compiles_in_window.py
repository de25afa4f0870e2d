"""Compile: programs that reached the compiler inside the window
(``jax.monitoring`` compile requests); must be 0."""


def read(ctx):
    return ctx["compile"]["requests_in_window"]
