"""Device: 1 - union of device-op intervals / traced slice, on the
idlest device."""


def read(ctx):
    t = ctx["trace"]
    share = t.idle_share() if t is not None else None
    return None if share is None else 100.0 * share
