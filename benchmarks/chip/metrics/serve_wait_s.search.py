"""Mean seconds from ``DSEService.submit`` until the executor starts
the point (queueing in the service's pool, store probe)."""


def read(r):
    waits = r["serve_wait_s"]
    return sum(waits) / len(waits) if waits else None
