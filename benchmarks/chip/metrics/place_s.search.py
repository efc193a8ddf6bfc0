"""Host seconds per completed point spent in packing and placement (`pack`,
`assign_ios`, `global_place`, `legalize`, `detailed_place`), from the
benchmark's spans around those calls."""


def read(r):
    per_point = r["place_s"]
    return sum(per_point.values()) / r["points"] if r["points"] else None
