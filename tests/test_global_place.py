"""Global placement (§3.4, Eq. 1): the star-model quadratic solve is one
compiled program per app shape, and its positions are the minimizer of
that quadratic, checked against a dense numpy solve. CPU only."""
import numpy as np
import pytest

from repro.core import trace
from repro.core.pnr.app import BENCH_APPS
from repro.core.pnr.global_place import (assign_ios, global_place,
                                         solver_programs)
from repro.core.pnr.packing import pack


@pytest.mark.parametrize("app_name,size,mem_cols", [
    ("tree_reduce", 8, ()),
    ("stencil", 8, (4,)),
])
def test_one_program_per_app_shape(app_name, size, mem_cols):
    packed = pack(BENCH_APPS[app_name]())
    fixed = assign_ios(packed, size, size)
    if mem_cols:
        assert any(i.kind == "mem" for i in packed.placeable.values())
    global_place(packed, size, size, mem_columns=mem_cols, fixed=fixed,
                 seed=0)
    programs = solver_programs()
    with trace.recording() as rec:
        for seed in (1, 2, 3):
            with trace.span("place.global"):
                global_place(packed, size, size, mem_columns=mem_cols,
                             fixed=fixed, seed=seed)
    assert solver_programs() == programs
    rows = [s for s in rec.spans if s.name == "place.global"]
    assert len(rows) == 3
    assert all(s.jit_n == 0 for s in rows)


def _star_minimizer(packed, fixed):
    """argmin_x Σ_net Σ_pins ||p − mean(net pins)||² over the movable
    instances, by a dense solve of its normal equations."""
    movable = [n for n in packed.placeable if n not in fixed]
    idx = {n: i for i, n in enumerate(movable)}
    a = np.zeros((len(movable), len(movable)))
    b = np.zeros((len(movable), 2))
    for net in packed.nets:
        members = [net.src[0]] + [s for s, _ in net.sinks]
        members = [m for m in members if m in packed.placeable]
        k = len(members)
        if k < 2:
            continue
        # pins -> movable incidence, and the fixed pins' coordinates
        inc = np.zeros((k, len(movable)))
        fix = np.zeros((k, 2))
        for j, m in enumerate(members):
            if m in idx:
                inc[j, idx[m]] = 1.0
            else:
                fix[j] = fixed[m]
        proj = np.eye(k) - np.full((k, k), 1.0 / k)
        a += inc.T @ proj @ inc
        b -= inc.T @ proj @ fix
    return {n: tuple(v) for n, v in zip(movable, np.linalg.solve(a, b))}


@pytest.mark.parametrize("size", [6, 8])
@pytest.mark.parametrize("app_name", sorted(BENCH_APPS))
def test_matches_dense_star_model_solve(app_name, size):
    packed = pack(BENCH_APPS[app_name]())
    fixed = assign_ios(packed, size, size)
    ref = _star_minimizer(packed, fixed)
    pos = global_place(packed, size, size, fixed=fixed, seed=5)
    for name, (rx, ry) in ref.items():
        # inside the fabric, so the solver's clip leaves it alone
        assert 0.0 <= rx <= size - 1 and 0.0 <= ry <= size - 1
        assert pos[name] == pytest.approx((rx, ry), abs=1e-3), name
    for name, xy in fixed.items():
        assert pos[name] == xy
