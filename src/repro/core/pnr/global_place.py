"""Analytical global placement (§3.4, Eq. 1).

Minimizes Σ_net ( HPWL_estimate + MEM_potential ) where the HPWL estimate is
the quadratic (L2) star model — "In global placement, we use L2 distance to
approximate the HPWL to speed up the algorithm" — solved with the standard
conjugate gradient method (the paper cites APlace's CG approach). Memory
legalization is the usual anchor-iteration: each outer round adds springs
pulling MEM instances to their nearest legal column, then re-solves.

The quadratic solve runs in JAX (matvec + jax.scipy CG). All outer
rounds, memory anchoring included, are one jitted program, compiled once
per app shape and reused by every later call with that shape.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.scipy.sparse.linalg import cg as jax_cg

from .packing import PackedGraph


def _io_ring_positions(w: int, h: int) -> List[Tuple[int, int]]:
    """Clockwise ring coordinates, corners excluded (a corner tile with
    depopulated SB sides can have no legal fabric connection)."""
    ring = [(x, 0) for x in range(1, w - 1)]
    ring += [(w - 1, y) for y in range(1, h - 1)]
    ring += [(x, h - 1) for x in range(w - 2, 0, -1)]
    ring += [(0, y) for y in range(h - 2, 0, -1)]
    return ring


def assign_ios(packed: PackedGraph, w: int, h: int) -> Dict[str,
                                                            Tuple[int, int]]:
    """Spread IO instances evenly around the array border."""
    ios = [n for n, inst in packed.placeable.items()
           if inst.kind in ("io_in", "io_out")]
    ring = _io_ring_positions(w, h)
    if len(ios) > len(ring):
        raise ValueError("more IOs than border tiles")
    stride = max(1, len(ring) // max(len(ios), 1))
    return {name: ring[(i * stride) % len(ring)]
            for i, name in enumerate(ios)}


@functools.partial(jax.jit, static_argnames=(
    "n_nets", "outer_iters", "cg_tol", "anchor_mems"))
def _global_solve(pin_net: jnp.ndarray, pin_mov: jnp.ndarray,
                  pin_fix: jnp.ndarray, x0: jnp.ndarray, hi: jnp.ndarray,
                  is_mem: jnp.ndarray, mem_cols: jnp.ndarray, *,
                  n_nets: int, outer_iters: int, cg_tol: float,
                  anchor_mems: bool) -> jnp.ndarray:
    """All ``outer_iters`` anchor rounds of the quadratic solve.

    pin_net/pin_mov: (n_pins,) net id and movable index (-1: fixed pin);
    pin_fix: (n_pins, 2) fixed pin positions; x0: (n_mov, 2) start;
    hi: (2,) clip limits; is_mem: (n_mov,); mem_cols: (n_cols,) sorted.
    """
    n_mov = x0.shape[0]
    segs = max(n_nets, 1)
    mov = jnp.clip(pin_mov, 0, n_mov - 1)
    is_mov = (pin_mov >= 0)[:, None]
    net_size = jax.ops.segment_sum(jnp.ones_like(pin_net, jnp.float32),
                                   pin_net, num_segments=segs)

    def grad_quadratic(x, anchor_w, anchor_p):
        """Gradient of Σ_net Σ_pins ||p − c_net||² + Σ anchors, wrt x."""
        p = jnp.where(is_mov, x[mov], pin_fix)
        c = (jax.ops.segment_sum(p, pin_net, num_segments=segs)
             / jnp.maximum(net_size, 1.0)[:, None])
        resid = p - c[pin_net]
        g = jnp.zeros_like(x).at[mov].add(jnp.where(is_mov, resid, 0.0))
        g = g + anchor_w[:, None] * (x - anchor_p)
        return 2.0 * g

    # The cost is quadratic ⇒ grad is affine in x: solve A x = b with CG,
    # where A x = grad(x) − grad(0) and b = −grad(0).
    x = x0
    anchor_w = jnp.zeros((n_mov,), jnp.float32)
    anchor_p = jnp.zeros((n_mov, 2), jnp.float32)
    for outer in range(outer_iters):
        g0 = grad_quadratic(jnp.zeros_like(x), anchor_w, anchor_p)

        def matvec(v):
            return (grad_quadratic(v.reshape(n_mov, 2), anchor_w, anchor_p)
                    - g0).reshape(-1)

        sol, _ = jax_cg(matvec, (-g0).reshape(-1), x0=x.reshape(-1),
                        tol=cg_tol, maxiter=200)
        x = jnp.clip(sol.reshape(n_mov, 2), 0.0, hi)

        # MEM_potential: anchor memories to their nearest legal column
        if anchor_mems:
            col = mem_cols[jnp.argmin(
                jnp.abs(x[:, :1] - mem_cols[None, :]), axis=1)]
            anchor_p = x.at[:, 0].set(jnp.where(is_mem, col, x[:, 0]))
            anchor_w = jnp.where(is_mem, 0.5 * (outer + 1), 0.0
                                 ).astype(jnp.float32)
    return x


def solver_programs() -> int:
    """Compiled programs the global-placement solver holds: one per
    distinct app shape (movable count, pin count, net count, memory
    columns) seen by this process."""
    return _global_solve._cache_size()


def global_place(packed: PackedGraph, width: int, height: int,
                 mem_columns: Sequence[int] = (),
                 fixed: Optional[Dict[str, Tuple[int, int]]] = None,
                 outer_iters: int = 4, cg_tol: float = 1e-5,
                 seed: int = 0) -> Dict[str, Tuple[float, float]]:
    """Continuous positions for every placeable instance (fixed IOs pinned).

    Returns name -> (x, y) float positions (pre-legalization).
    """
    if fixed is None:
        fixed = assign_ios(packed, width, height)

    movable = [n for n in packed.placeable if n not in fixed]
    m_idx = {n: i for i, n in enumerate(movable)}
    n_mov = len(movable)
    is_mem = np.array(
        [packed.placeable[n].kind == "mem" for n in movable], dtype=bool)

    if n_mov == 0:
        return {k: (float(x), float(y)) for k, (x, y) in fixed.items()}

    # ---- net pin tables ---------------------------------------------------
    pin_net: List[int] = []
    pin_mov: List[int] = []          # movable index or -1
    pin_fix: List[Tuple[float, float]] = []
    n_nets = 0
    for net in packed.nets:
        members = [net.src[0]] + [s for s, _ in net.sinks]
        members = [m for m in members if m in packed.placeable]
        if len(members) < 2:
            continue
        for mname in members:
            pin_net.append(n_nets)
            if mname in m_idx:
                pin_mov.append(m_idx[mname])
                pin_fix.append((0.0, 0.0))
            else:
                pin_mov.append(-1)
                fx, fy = fixed[mname]
                pin_fix.append((float(fx), float(fy)))
        n_nets += 1

    rng = np.random.default_rng(seed)
    x0 = rng.uniform([width * .25, height * .25],
                     [width * .75, height * .75],
                     size=(n_mov, 2)).astype(np.float32)
    mem_cols = np.array(sorted(mem_columns), np.float32)
    x = _global_solve(
        np.array(pin_net, np.int32), np.array(pin_mov, np.int32),
        np.array(pin_fix, np.float32).reshape(-1, 2), x0,
        np.array([width - 1.0, height - 1.0], np.float32), is_mem,
        mem_cols, n_nets=n_nets, outer_iters=outer_iters, cg_tol=cg_tol,
        anchor_mems=bool(len(mem_cols) and is_mem.any()))

    out = {k: (float(px), float(py)) for k, (px, py) in fixed.items()}
    xx = np.asarray(x)
    for name, i in m_idx.items():
        out[name] = (float(xx[i, 0]), float(xx[i, 1]))
    return out


def legalize(packed: PackedGraph, positions: Dict[str, Tuple[float, float]],
             width: int, height: int, mem_columns: Sequence[int] = (),
             io_ring: bool = True,
             fixed: Optional[Dict[str, Tuple[int, int]]] = None
             ) -> Dict[str, Tuple[int, int]]:
    """Snap continuous positions to distinct legal tiles (greedy nearest)."""
    mem_cols = set(mem_columns)
    occupied: Dict[Tuple[int, int], str] = {}
    out: Dict[str, Tuple[int, int]] = {}
    fixed = fixed or {}

    def legal_for(inst_kind: str, x: int, y: int) -> bool:
        border = x in (0, width - 1) or y in (0, height - 1)
        if inst_kind in ("io_in", "io_out"):
            return border if io_ring else True
        if io_ring and border:
            return False
        if inst_kind == "mem":
            return x in mem_cols if mem_cols else True
        return x not in mem_cols           # PEs keep off mem columns

    for name, pos in fixed.items():
        occupied[pos] = name
        out[name] = pos

    order = sorted((n for n in packed.placeable if n not in fixed),
                   key=lambda n: (packed.placeable[n].kind != "mem",
                                  positions[n]))
    for name in order:
        kind = packed.placeable[name].kind
        px, py = positions[name]
        best = None
        for r in range(width + height):
            cands = []
            for dx in range(-r, r + 1):
                for dy in (-r + abs(dx), r - abs(dx)):
                    x, y = int(round(px)) + dx, int(round(py)) + dy
                    if 0 <= x < width and 0 <= y < height \
                            and (x, y) not in occupied \
                            and legal_for(kind, x, y):
                        cands.append((abs(x - px) + abs(y - py), x, y))
            if cands:
                _, x, y = min(cands)
                best = (x, y)
                break
        if best is None:
            raise ValueError(f"cannot legalize {name} ({kind})")
        occupied[best] = name
        out[name] = best
    return out
