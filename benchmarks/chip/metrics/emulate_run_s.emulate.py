"""Seconds per batch in the program's ``emulate.run`` spans
(``run_apps_batch``: stimulus packing, the device scan and the outputs
back on the host)."""


def read(r):
    total = r.get("emulate_run_s")
    return total / r["points"] if total is not None and r["points"] else None
