"""Service: ``daemon.stats()["admit_wait_p50_s"]`` — the dispatcher's
own median of admission to leaving the queue, the solve excluded."""


def read(ctx):
    svc = ctx["records"].get("service") or {}
    wait = svc.get("admit_wait_p50_s")
    return None if wait is None else 1e3 * wait
