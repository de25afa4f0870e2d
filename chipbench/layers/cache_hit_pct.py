"""Compile: of the programs that reached the compiler in this process,
the share the persistent cache served."""


def read(ctx):
    c = ctx["compile"]
    return 100.0 * c["hits"] / c["requests"] if c["requests"] else None
