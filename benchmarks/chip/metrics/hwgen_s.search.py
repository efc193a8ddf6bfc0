"""Host seconds per completed point spent in the pass pipeline
(`PassManager.run`) and static analysis (`analyze`), from the
benchmark's spans around those calls."""


def read(r):
    per_point = r["hwgen_s"]
    return sum(per_point.values()) / r["points"] if r["points"] else None
