"""Programs JAX compiled or loaded from its persistent cache inside the
window (its backend-compile event)."""


def read(r):
    return r["compiles"]
