"""Host seconds per completed point spent in routing (`route_app`) and
building its `RoutingResources`, from the benchmark's spans around those
calls."""


def read(r):
    per_point = r["route_s"]
    return sum(per_point.values()) / r["points"] if r["points"] else None
