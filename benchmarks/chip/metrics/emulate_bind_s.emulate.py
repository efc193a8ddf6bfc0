"""Host seconds per batch in the program's ``emulate.bind`` spans:
``AppEmulator.from_pnr`` for every app of the batch (``route_to_config``
and ``depth_for_route`` on the host)."""


def read(r):
    total = r.get("emulate_bind_s")
    return total / r["points"] if total is not None and r["points"] else None
