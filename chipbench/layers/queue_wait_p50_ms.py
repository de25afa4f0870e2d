"""Service: ``daemon.stats()["wait_p50_s"]`` — the dispatcher's own
median of admission-to-resolution time."""


def read(ctx):
    svc = ctx["records"].get("service")
    return None if not svc else 1e3 * svc["wait_p50_s"]
