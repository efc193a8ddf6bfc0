"""Pallas kernel: blocked min-plus (tropical) relaxation for routing
wavefronts.

Hardware adaptation (DESIGN.md §2): per-net A* is pointer-chasing and has
no TPU analogue, so the wavefront-cost computation is reformulated as
iterated tropical matrix-vector products over the (tile-level) routing
graph:

    d'[b, j] = min(d[b, j], min_i (d[b, i] + w[i, j]))

for a *batch* of nets b at once. ``w`` is the dense inf-padded adjacency
of the coarse routing graph (tiles, not IR nodes: N = W*H <= 4096, so the
dense tile fits VMEM in 128x128 blocks). Iterating to fixpoint yields all
shortest path costs (Bellman-Ford over the tropical semiring); the
PathFinder outer loop (``repro.core.pnr.route``, ``strategy="minplus"``)
uses these cost fields as its batched A* lower bounds.

``minplus_wavefront`` is the router-facing entry point: it relaxes in
blocks and stops as soon as a block leaves the field unchanged, so the
iteration count adapts to the graph diameter instead of paying the full
Bellman-Ford ``N - 1`` bound. The block loop and its convergence test
run on the device in one compiled program per seed batch and tile count
(``_wavefront``): a call is one dispatch, with no host sync per block.
``engine="auto"`` runs the Pallas kernel compiled on TPU and the dense
reference elsewhere (interpret mode would only be slower).

The kernel compiles for TPU v5e (``tests/test_tpu_compile.py``: 1024
tiles, batches up to 1024). Its scoped VMEM grows with the batch rows of
a block — 1024 rows in one block asked for 18 MiB of the 16 MiB limit —
so the batch axis is gridded in blocks of at most ``BATCH_BLOCK`` rows.
Rows are independent, so the blocking leaves every result unchanged.

Validated in interpret mode against ``ref.minplus_ref`` /
``ref.minplus_fixpoint_ref`` and against host Dijkstra in
``tests/test_route_minplus.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128
BATCH_BLOCK = 256      # batch rows per grid step (scoped-VMEM bound)
INF = jnp.float32(3.0e38) / 4


def _default_interpret() -> bool:
    """Compiled on TPU, interpret elsewhere (CPU has no Mosaic backend).

    Resolved per call, here and in ``hpwl`` and ``ops``: a mid-process
    backend swap must not see a stale value."""
    return jax.default_backend() != "tpu"


def _minplus_kernel(d_ref, w_ref, out_ref):
    """d: (BB, BLOCK_i) costs; w: (BLOCK_i, BLOCK_j); out: (BB, BLOCK_j).

    Accumulates the running minimum across the i-grid dimension.
    """
    i = pl.program_id(2)
    d = d_ref[...]                              # (B, bi)
    w = w_ref[...]                              # (bi, bj)
    cand = jnp.min(d[:, :, None] + w[None, :, :], axis=1)   # (B, bj)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = cand

    @pl.when(i > 0)
    def _acc():
        out_ref[...] = jnp.minimum(out_ref[...], cand)


@functools.partial(jax.jit, static_argnames=("interpret",))
@jax.named_scope("canal.minplus")
def minplus_step(d: jnp.ndarray, w: jnp.ndarray,
                 interpret: bool = True) -> jnp.ndarray:
    """One relaxation: returns min(d, d ⊗ w) for batched cost vectors.

    d: (B, N) float32; w: (N, N) float32 inf-padded adjacency (w[i,i]=0).
    """
    b, n = d.shape
    n_pad = pl.cdiv(n, BLOCK) * BLOCK
    # one block holds the whole batch when it fits, else BATCH_BLOCK-row
    # blocks (a multiple of the 8-row sublane tile) over a padded batch
    bb = b if b <= BATCH_BLOCK else BATCH_BLOCK
    b_pad = pl.cdiv(b, bb) * bb
    d_p = jnp.pad(d, ((0, b_pad - b), (0, n_pad - n)), constant_values=INF)
    w_p = jnp.pad(w, ((0, n_pad - n), (0, n_pad - n)), constant_values=INF)
    # (rows, j, i): i innermost accumulates the running minimum
    grid = (b_pad // bb, n_pad // BLOCK, n_pad // BLOCK)
    out = pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, BLOCK), lambda r, j, i: (r, i)),
            pl.BlockSpec((BLOCK, BLOCK), lambda r, j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, BLOCK), lambda r, j, i: (r, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(d_p, w_p)
    return jnp.minimum(d, out[:b, :n])


def minplus_fixpoint(d0: jnp.ndarray, w: jnp.ndarray, iters: int,
                     interpret: bool = True) -> jnp.ndarray:
    """Iterate to (bounded) fixpoint: all-sources shortest path costs."""

    def body(_, d):
        return minplus_step(d, w, interpret=interpret)

    return jax.lax.fori_loop(0, iters, body, d0)


@functools.partial(jax.jit, static_argnames=("iters",))
@jax.named_scope("canal.minplus")
def _ref_block(d: jnp.ndarray, w: jnp.ndarray, iters: int) -> jnp.ndarray:
    """``iters`` dense relaxations of the pure-jnp oracle under one jit."""

    def body(_, dd):
        return jnp.minimum(dd, jnp.min(dd[:, :, None] + w[None], axis=1))

    return jax.lax.fori_loop(0, iters, body, d)


@functools.partial(jax.jit, static_argnames=("block_iters", "use_kernel",
                                             "interpret"))
@jax.named_scope("canal.minplus")
def _wavefront(d: jnp.ndarray, w: jnp.ndarray, block_iters: int,
               use_kernel: bool, interpret: bool
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The whole adaptive wavefront as one program: blocks of
    ``block_iters`` relaxations until a block leaves the field unchanged
    or the ``N - 1`` cap is reached. Returns the field and the number of
    blocks run."""
    n = w.shape[0]
    max_blocks = max(1, -(-max(n - 1, 1) // block_iters))

    def more(state):
        _, blocks, done = state
        return (blocks < max_blocks) & ~done

    def block(state):
        d, blocks, _ = state
        if use_kernel:
            nd = minplus_fixpoint(d, w, block_iters, interpret=interpret)
        else:
            nd = _ref_block(d, w, block_iters)
        return nd, blocks + 1, jnp.all(nd == d)

    d, blocks, _ = jax.lax.while_loop(
        more, block, (d, jnp.int32(0), jnp.bool_(False)))
    return d, blocks


def wavefront_programs() -> int:
    """Compiled wavefront programs this process holds: one per seed
    bucket and tile count (and engine) seen."""
    return _wavefront._cache_size()


def minplus_wavefront_blocks(d0: jnp.ndarray, w: jnp.ndarray,
                             block_iters: int = 8,
                             engine: str = "auto",
                             interpret: Optional[bool] = None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Relax ``d0`` to the true shortest-path fixpoint, adaptively;
    returns the field and the number of blocks run, both on the device.

    Runs ``block_iters`` relaxations per block and stops when a block
    leaves the field unchanged (a min-plus fixpoint is stable, so one
    unchanged block proves convergence); a cap of ``N - 1`` total
    relaxations preserves the Bellman-Ford bound on adversarial graphs.
    The loop and its test run on the device: one dispatch per call, and
    one compiled program per input shape.

    engine: ``"pallas"`` forces the blocked kernel, ``"ref"`` the dense
    reference, ``"auto"`` picks the kernel only where it compiles
    (TPU) — on interpret-mode hosts the reference is the faster exact
    implementation of the same contract.
    """
    if interpret is None:
        interpret = _default_interpret()
    if engine not in ("auto", "pallas", "ref"):
        raise ValueError(f"unknown minplus engine {engine!r}")
    use_kernel = engine == "pallas" or (engine == "auto" and not interpret)
    # the reference has no interpret mode: one program key either way
    return _wavefront(jnp.asarray(d0, jnp.float32),
                      jnp.asarray(w, jnp.float32), block_iters=block_iters,
                      use_kernel=use_kernel,
                      interpret=bool(interpret) and use_kernel)


def minplus_wavefront(d0: jnp.ndarray, w: jnp.ndarray,
                      block_iters: int = 8,
                      engine: str = "auto",
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """The converged field of :func:`minplus_wavefront_blocks`."""
    return minplus_wavefront_blocks(d0, w, block_iters, engine,
                                    interpret)[0]
