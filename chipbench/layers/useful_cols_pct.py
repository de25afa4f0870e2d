"""Service: columns asked for over columns computed — the sum of
``batch_k`` over the sum of ``bucket`` over the tickets' answers."""


def read(ctx):
    fills = ctx["records"].get("fills")
    if not fills:
        return None
    return 100.0 * sum(k for k, _ in fills) / sum(b for _, b in fills)
