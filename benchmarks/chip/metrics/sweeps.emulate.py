"""Fixpoint sweeps per batch, the ``sweeps`` counter of the program's
``emulate.run`` spans: the static loop bound (the deepest app's sweep
count) times the cycles, which every lane of the batch pays."""


def read(r):
    total = r.get("sweeps")
    return total / r["points"] if total is not None and r["points"] else None
