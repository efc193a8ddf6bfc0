"""Pallas kernels: fabric combinational sweeps (the config-sweep /
emulation hot spot of the generated interconnect).

One sweep computes, for every IR node, the value of its selected mux input:

    out[i] = vals[src[i, sel[i]]]

Three kernels share that structure:

``fabric_sweep``
    One sweep, one configuration. The node-value vector lives wholly in
    VMEM (N <= ~64k nodes => <= 256 KiB int32, well under the ~16 MiB VMEM
    budget) while the fan-in table streams block-by-block.

``fabric_sweep_batch``
    One sweep, B configurations (bitstream-major layout): the value matrix
    is blocked over configs, the shared fan-in table over nodes.

``fabric_fused_run``
    The streamed multi-cycle engine: a whole *T-cycle emulation* runs in
    one kernel invocation, with the external IO stream gridded over
    ``chunk``-cycle blocks so only ``(FUSED_LANES, chunk, io)`` of the
    ``(B, T, io)`` stimulus ever sits in VMEM — the rest stays in HBM and
    streams in per grid step (long traces no longer materialize next to
    the value matrices). Register/memory state lives in a
    ``(FUSED_LANES, S)`` state-vector output that persists across the
    (sequential) T-chunk grid steps and re-initializes when a new lane
    block starts; per cycle the pinned sources are gathered scatter-free
    out of that state vector through a node→state index map (``pin_src``).

``fabric_fused_batch``
    The fused batched engine: the *entire* fixpoint (``max_depth`` sweeps)
    for a block of ``FUSED_LANES`` configurations runs inside a single
    kernel invocation. VMEM layout, per grid step ``i``:

    * ``vals``/``sel``/``pin_vals`` — (FUSED_LANES, NP) lane-major value,
      mux-select and pinned-source matrices, where NP rounds N+1 up to the
      128-lane boundary so index N doubles as the zero sentinel;
    * ``src`` (NP, F), ``keep``/``pin_mask``/``pe_res_idx`` (NP,) — the
      node tables, resident and shared by every lane of every block;
    * ``op``/``const`` (FUSED_LANES, P) and ``imm_mask``/``imm_val``
      (FUSED_LANES, P, 4) — the PE programs, resident next to the values
      so PE cores evaluate *in-kernel* (no Python-level round-trip between
      sweeps), applied scatter-free through ``pe_res_idx``: node i with
      ``pe_res_idx[i] < 2P`` reads its value out of the flattened
      (res0, res1) PE result vector;
    * ``depths`` (FUSED_LANES,) — per-configuration sweep counts.

    Masking scheme: every lane runs the static ``max_depth`` loop, but a
    lane whose own combinational depth ``depths[b]`` is reached keeps its
    value vector frozen (``where(t < depths[b], new, old)``). Each lane
    therefore performs exactly its configuration's fixpoint — bit-identical
    to a serial per-config run even when another lane in the batch needs
    more sweeps (and even for adversarial configs with combinational
    cycles, whose values depend on the sweep count).

Validated in interpret mode against ``ref.fabric_sweep_ref`` /
``ref.fabric_fused_batch_ref``. None of the four compiles for TPU:
Mosaic refuses the 1-D ``jnp.take`` of the sweep kernels
(``_gather_lowering_rule``) and the fused kernels' gathers ("Only 2D
gather is supported"). They run in interpret mode only, and
``FabricModule(use_pallas=True)`` refuses a TPU backend; the served
engine is the XLA one, ``ref.fabric_fused_batch_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 512          # nodes per block (multiple of 128 lanes x 4 sublanes)
FUSED_LANES = 8        # configurations per fused-kernel block

# PE ALU candidate order; must match repro.core.tiles.PECore.OPS
# (repro.core.lowering asserts the correspondence at import time).
PE_OPS = ("add", "sub", "mul", "and", "or", "xor", "shl", "shr", "min",
          "max", "abs", "sel", "const", "pass")


def _default_interpret() -> bool:
    """Compiled on TPU, interpret elsewhere (CPU has no Mosaic backend).

    Resolved *per call*: tests and tools that swap ``jax.default_backend``
    (or force a platform mid-process) must not see a stale cached value.
    """
    return jax.default_backend() != "tpu"


def pe_alu_candidates(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
                      const: jnp.ndarray) -> jnp.ndarray:
    """All PE ALU results, stacked (n_ops, P) in ``PE_OPS`` order.

    Single source of truth for the PE datapath: the fused kernel, its
    pure-jnp oracle and the unfused ``FabricModule._eval_pes`` all select
    rows out of this stack with the configured opcode."""
    shift_b = jnp.clip(b, 0, 15)
    return jnp.stack([
        a + b, a - b, a * b, a & b, a | b, a ^ b,
        a << shift_b, a >> shift_b, jnp.minimum(a, b),
        jnp.maximum(a, b), jnp.abs(a - b),
        jnp.where((a & 1) == 1, b, c), const, a,
    ], axis=0)


def _sweep_kernel(vals_ref, src_ref, sel_ref, out_ref):
    """vals: (Npad,) resident; src: (BLOCK_N, F); sel: (BLOCK_N,)."""
    src = src_ref[...]                        # (BN, F) int32
    sel = sel_ref[...]                        # (BN,) int32
    picked = jnp.take_along_axis(src, sel[:, None], axis=1)[:, 0]
    out_ref[...] = jnp.take(vals_ref[...], picked, axis=0)


def fabric_sweep(vals_ext: jnp.ndarray, src: jnp.ndarray, sel: jnp.ndarray,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """One sweep. vals_ext: (N+1,) with zero sentinel at N; src: (N, F)
    int32 (sentinel-padded); sel: (N,). Returns (N,).

    ``interpret=None`` resolves from the backend *before* the jit
    boundary (the jit cache must key on the resolved bool, or a backend
    swap would replay a stale trace): compiled on TPU, interpret mode
    everywhere else."""
    if interpret is None:
        interpret = _default_interpret()
    return _fabric_sweep_jit(vals_ext, src, sel, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fabric_sweep_jit(vals_ext: jnp.ndarray, src: jnp.ndarray,
                      sel: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    n, f = src.shape
    n_pad = pl.cdiv(n, BLOCK_N) * BLOCK_N
    v_pad = pl.cdiv(vals_ext.shape[0], 128) * 128
    vals_p = jnp.pad(vals_ext, (0, v_pad - vals_ext.shape[0]))
    src_p = jnp.pad(src, ((0, n_pad - n), (0, 0)))
    sel_p = jnp.pad(sel, (0, n_pad - n))
    grid = (n_pad // BLOCK_N,)
    out = pl.pallas_call(
        _sweep_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((v_pad,), lambda i: (0,)),          # resident vals
            pl.BlockSpec((BLOCK_N, f), lambda i: (i, 0)),    # streamed fan-in
            pl.BlockSpec((BLOCK_N,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(vals_p, src_p, sel_p)
    return out[:n]


def _sweep_batch_kernel(vals_ref, src_ref, sel_ref, out_ref):
    """vals: (BB, Npad); src: (BLOCK_N, F); sel: (BB, BLOCK_N)."""
    src = src_ref[...]
    bb = vals_ref.shape[0]

    def body(b, _):
        sel = sel_ref[b]
        picked = jnp.take_along_axis(src, sel[:, None], axis=1)[:, 0]
        out_ref[b, :] = jnp.take(vals_ref[b], picked, axis=0)
        return 0

    jax.lax.fori_loop(0, bb, body, 0)


def fabric_sweep_batch(vals_ext: jnp.ndarray, src: jnp.ndarray,
                       sel: jnp.ndarray, interpret: Optional[bool] = None
                       ) -> jnp.ndarray:
    """Batched sweep over configurations. vals_ext: (B, N+1); sel: (B, N);
    src shared. Returns (B, N). ``interpret=None`` resolves from the
    backend per call, before the jit boundary (compiled on TPU, interpret
    elsewhere)."""
    if interpret is None:
        interpret = _default_interpret()
    return _fabric_sweep_batch_jit(vals_ext, src, sel, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fabric_sweep_batch_jit(vals_ext: jnp.ndarray, src: jnp.ndarray,
                            sel: jnp.ndarray, interpret: bool
                            ) -> jnp.ndarray:
    b = vals_ext.shape[0]
    n, f = src.shape
    bb = 8                                     # configs per block
    b_pad = pl.cdiv(b, bb) * bb
    n_pad = pl.cdiv(n, BLOCK_N) * BLOCK_N
    v_pad = pl.cdiv(vals_ext.shape[1], 128) * 128
    vals_p = jnp.pad(vals_ext,
                     ((0, b_pad - b), (0, v_pad - vals_ext.shape[1])))
    src_p = jnp.pad(src, ((0, n_pad - n), (0, 0)))
    sel_p = jnp.pad(sel, ((0, b_pad - b), (0, n_pad - n)))
    grid = (b_pad // bb, n_pad // BLOCK_N)
    out = pl.pallas_call(
        _sweep_batch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, v_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_N, f), lambda i, j: (j, 0)),
            pl.BlockSpec((bb, BLOCK_N), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, BLOCK_N), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), jnp.int32),
        interpret=interpret,
    )(vals_p, src_p, sel_p)
    return out[:b, :n]


def _fused_batch_kernel(depths_ref, vals_ref, sel_ref, pin_vals_ref,
                        op_ref, const_ref, imm_mask_ref, imm_val_ref,
                        src_ref, keep_ref, pin_mask_ref, pe_in_ref,
                        pe_res_idx_ref, out_ref, *, max_depth: int,
                        word: int):
    """One block: FUSED_LANES configurations, the whole fixpoint in VMEM.

    Per sweep and lane: gather the selected fan-in, hold undriven nodes,
    re-pin sources (registers / external IO / memory reads), evaluate the
    PE ALUs and place their results scatter-free via ``pe_res_idx`` — then
    freeze the lane once its own ``depths[b]`` sweeps have run."""
    src = src_ref[...]                              # (NP, F)
    keep = keep_ref[...]                            # (NP,)
    pin_mask = pin_mask_ref[...]                    # (NP,)
    pe_in = pe_in_ref[...]                          # (P, 4)
    pe_res_idx = pe_res_idx_ref[...]                # (NP,)
    np_, f = src.shape
    p = pe_in.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (np_, 1), 0)[:, 0] * f
    src_flat = src.reshape(-1)
    pe_in_flat = pe_in.reshape(-1)
    is_pe_out = pe_res_idx < 2 * p

    def lane(b, carry):
        sel = sel_ref[b, :]
        pin_vals = pin_vals_ref[b, :]
        op = op_ref[b, :]
        const = const_ref[b, :]
        imm_mask = imm_mask_ref[b, :, :]
        imm_val = imm_val_ref[b, :, :]
        d = depths_ref[b]
        # the selected source of every node is sweep-invariant
        picked = jnp.take(src_flat, rows + sel)

        def sweep(t, v):
            nv = jnp.take(v, picked)
            nv = jnp.where(keep > 0, v, nv)
            nv = jnp.where(pin_mask > 0, pin_vals, nv)
            ins = jnp.take(nv, pe_in_flat).reshape(p, 4)
            ins = jnp.where(imm_mask > 0, imm_val, ins)
            a_, b_, c_ = ins[:, 0], ins[:, 1], ins[:, 2]
            cand = pe_alu_candidates(a_, b_, c_, const)
            res0 = jnp.take_along_axis(cand, op[None, :], axis=0)[0] & word
            res1 = a_ & word
            res = jnp.concatenate(
                [jnp.stack([res0, res1], axis=1).reshape(-1),
                 jnp.zeros(1, jnp.int32)])
            nv = jnp.where(is_pe_out, jnp.take(res, pe_res_idx), nv)
            return jnp.where(t < d, nv, v)

        out_ref[b, :] = jax.lax.fori_loop(0, max_depth, sweep,
                                          vals_ref[b, :])
        return carry

    jax.lax.fori_loop(0, FUSED_LANES, lane, 0)


def fabric_fused_batch(vals0: jnp.ndarray, sel: jnp.ndarray,
                       pin_vals: jnp.ndarray, depths: jnp.ndarray,
                       op: jnp.ndarray, const: jnp.ndarray,
                       imm_mask: jnp.ndarray, imm_val: jnp.ndarray,
                       src: jnp.ndarray, keep: jnp.ndarray,
                       pin_mask: jnp.ndarray, pe_in: jnp.ndarray,
                       pe_res_idx: jnp.ndarray, max_depth: int,
                       word: int = 0xFFFF,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused batched fixpoint: ``max_depth`` masked sweeps with in-kernel
    PE evaluation, one kernel invocation per FUSED_LANES configurations.

    vals0/sel/pin_vals: (B, N); depths: (B,) per-lane sweep counts;
    op/const: (B, P); imm_mask/imm_val: (B, P, 4); src: (N, F) with
    sentinel N for absent fan-in; keep/pin_mask: (N,) int32 flags;
    pe_in: (P, 4) node indices (sentinel N); pe_res_idx: (N,) index into
    the flattened (res0, res1) PE result vector, 2P when the node is not a
    PE output. Returns the (B, N) value matrix after the fixpoint.
    ``interpret=None`` resolves from the backend per call, before the jit
    boundary."""
    if interpret is None:
        interpret = _default_interpret()
    return _fabric_fused_batch_jit(vals0, sel, pin_vals, depths, op,
                                   const, imm_mask, imm_val, src, keep,
                                   pin_mask, pe_in, pe_res_idx, max_depth,
                                   word, interpret)


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "word", "interpret"))
def _fabric_fused_batch_jit(vals0: jnp.ndarray, sel: jnp.ndarray,
                            pin_vals: jnp.ndarray, depths: jnp.ndarray,
                            op: jnp.ndarray, const: jnp.ndarray,
                            imm_mask: jnp.ndarray, imm_val: jnp.ndarray,
                            src: jnp.ndarray, keep: jnp.ndarray,
                            pin_mask: jnp.ndarray, pe_in: jnp.ndarray,
                            pe_res_idx: jnp.ndarray, max_depth: int,
                            word: int, interpret: bool) -> jnp.ndarray:
    b, n = vals0.shape
    f = src.shape[1]
    p = pe_in.shape[0]
    bb = FUSED_LANES
    b_pad = pl.cdiv(max(b, 1), bb) * bb
    # N+1 inside the padded region => index N is the zero sentinel
    n_pad = pl.cdiv(n + 1, 128) * 128
    db, dn = b_pad - b, n_pad - n
    vals_p = jnp.pad(vals0, ((0, db), (0, dn)))
    sel_p = jnp.pad(sel, ((0, db), (0, dn)))
    pin_vals_p = jnp.pad(pin_vals, ((0, db), (0, dn)))
    depths_p = jnp.pad(depths.astype(jnp.int32), (0, db))
    op_p = jnp.pad(op, ((0, db), (0, 0)))
    const_p = jnp.pad(const, ((0, db), (0, 0)))
    imm_mask_p = jnp.pad(imm_mask, ((0, db), (0, 0), (0, 0)))
    imm_val_p = jnp.pad(imm_val, ((0, db), (0, 0), (0, 0)))
    # padded nodes hold their (zero) value: src points at the sentinel,
    # keep=1, unpinned, not a PE output
    src_p = jnp.pad(src, ((0, dn), (0, 0)), constant_values=n)
    keep_p = jnp.pad(keep, (0, dn), constant_values=1)
    pin_mask_p = jnp.pad(pin_mask, (0, dn))
    pe_res_idx_p = jnp.pad(pe_res_idx, (0, dn), constant_values=2 * p)
    grid = (b_pad // bb,)
    out = pl.pallas_call(
        functools.partial(_fused_batch_kernel, max_depth=max_depth,
                          word=word),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb,), lambda i: (i,)),             # depths
            pl.BlockSpec((bb, n_pad), lambda i: (i, 0)),     # vals
            pl.BlockSpec((bb, n_pad), lambda i: (i, 0)),     # sel
            pl.BlockSpec((bb, n_pad), lambda i: (i, 0)),     # pin_vals
            pl.BlockSpec((bb, p), lambda i: (i, 0)),         # op
            pl.BlockSpec((bb, p), lambda i: (i, 0)),         # const
            pl.BlockSpec((bb, p, 4), lambda i: (i, 0, 0)),   # imm_mask
            pl.BlockSpec((bb, p, 4), lambda i: (i, 0, 0)),   # imm_val
            pl.BlockSpec((n_pad, f), lambda i: (0, 0)),      # src (shared)
            pl.BlockSpec((n_pad,), lambda i: (0,)),          # keep
            pl.BlockSpec((n_pad,), lambda i: (0,)),          # pin_mask
            pl.BlockSpec((p, 4), lambda i: (0, 0)),          # pe_in
            pl.BlockSpec((n_pad,), lambda i: (0,)),          # pe_res_idx
        ],
        out_specs=pl.BlockSpec((bb, n_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), jnp.int32),
        interpret=interpret,
    )(depths_p, vals_p, sel_p, pin_vals_p, op_p, const_p, imm_mask_p,
      imm_val_p, src_p, keep_p, pin_mask_p, jnp.asarray(pe_in),
      pe_res_idx_p)
    return out[:b, :n]


def _fused_run_kernel(depths_ref, sel_ref, op_ref, const_ref, imm_mask_ref,
                      imm_val_ref, ext_ref, src_ref, keep_ref, pin_mask_ref,
                      pin_src_ref, pe_in_ref, pe_res_idx_ref, reg_src_ref,
                      mem_in_ref, io_out_ref, obs_ref, state_ref, *,
                      max_depth: int, word: int, chunk: int, n_reg: int,
                      n_io: int, n_mem: int):
    """One grid step: FUSED_LANES configurations x ``chunk`` fabric cycles.

    The state vector (per lane) is laid out ``[regs | ext io | mem | 0]``;
    ``pin_src`` maps every pinned node into it, so per-cycle re-pinning is
    a gather (scatter-free, like the PE result placement). The state
    output block is pinned to t-block 0, so it survives the sequential
    walk over T chunks and is zeroed whenever a new lane block begins."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _reset_state():
        state_ref[...] = jnp.zeros_like(state_ref[...])

    src = src_ref[...]                              # (NP, F)
    keep = keep_ref[...]                            # (NP,)
    pin_mask = pin_mask_ref[...]                    # (NP,)
    pin_src = pin_src_ref[...]                      # (NP,)
    pe_in = pe_in_ref[...]                          # (P, 4)
    pe_res_idx = pe_res_idx_ref[...]                # (NP,)
    reg_src = reg_src_ref[...]                      # (Rp,)
    mem_in = mem_in_ref[...]                        # (Mp,)
    io_out = io_out_ref[...]                        # (IOp,)
    np_, f = src.shape
    p = pe_in.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (np_, 1), 0)[:, 0] * f
    src_flat = src.reshape(-1)
    pe_in_flat = pe_in.reshape(-1)
    is_pe_out = pe_res_idx < 2 * p

    def lane(b, carry):
        sel = sel_ref[b, :]
        op = op_ref[b, :]
        const = const_ref[b, :]
        imm_mask = imm_mask_ref[b, :, :]
        imm_val = imm_val_ref[b, :, :]
        d = depths_ref[b]
        ext = ext_ref[b, :, :]                      # (chunk, IOp)
        picked = jnp.take(src_flat, rows + sel)

        def cycle(c, st):
            if n_io:
                ext_c = jax.lax.dynamic_index_in_dim(ext, c, axis=0,
                                                     keepdims=False)
                st = st.at[n_reg:n_reg + n_io].set(ext_c[:n_io])
            pinned = jnp.take(st, pin_src)          # (NP,)
            v0 = jnp.where(pin_mask > 0, pinned, 0)

            def sweep(s, v):
                nv = jnp.take(v, picked)
                nv = jnp.where(keep > 0, v, nv)
                nv = jnp.where(pin_mask > 0, pinned, nv)
                ins = jnp.take(nv, pe_in_flat).reshape(p, 4)
                ins = jnp.where(imm_mask > 0, imm_val, ins)
                a_, b_, c_ = ins[:, 0], ins[:, 1], ins[:, 2]
                cand = pe_alu_candidates(a_, b_, c_, const)
                res0 = jnp.take_along_axis(cand, op[None, :],
                                           axis=0)[0] & word
                res1 = a_ & word
                res = jnp.concatenate(
                    [jnp.stack([res0, res1], axis=1).reshape(-1),
                     jnp.zeros(1, jnp.int32)])
                nv = jnp.where(is_pe_out, jnp.take(res, pe_res_idx), nv)
                return jnp.where(s < d, nv, v)

            v = jax.lax.fori_loop(0, max_depth, sweep, v0)
            obs_ref[b, c, :] = jnp.take(v, io_out)
            if n_reg:
                st = st.at[0:n_reg].set(jnp.take(v, reg_src)[:n_reg])
            if n_mem:
                st = st.at[n_reg + n_io:n_reg + n_io + n_mem].set(
                    jnp.take(v, mem_in)[:n_mem])
            return st

        state_ref[b, :] = jax.lax.fori_loop(0, chunk, cycle,
                                            state_ref[b, :])
        return carry

    jax.lax.fori_loop(0, FUSED_LANES, lane, 0)


def fabric_fused_run(sel: jnp.ndarray, ext: jnp.ndarray,
                     depths: jnp.ndarray, op: jnp.ndarray,
                     const: jnp.ndarray, imm_mask: jnp.ndarray,
                     imm_val: jnp.ndarray, src: jnp.ndarray,
                     keep: jnp.ndarray, pin_mask: jnp.ndarray,
                     pin_src: jnp.ndarray, pe_in: jnp.ndarray,
                     pe_res_idx: jnp.ndarray, reg_src: jnp.ndarray,
                     mem_in: jnp.ndarray, io_out: jnp.ndarray,
                     n_reg: int, n_io: int, n_mem: int, max_depth: int,
                     chunk: int = 8, word: int = 0xFFFF,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Streamed fused emulation: T cycles in one kernel, ext-IO from HBM.

    sel: (B, N) mux selects; ext: (B, T, n_io) stimulus (streamed in
    ``chunk``-cycle grid blocks); depths: (B,) per-lane sweep counts;
    op/const: (B, P); imm_mask/imm_val: (B, P, 4); src/keep/pin_mask/
    pe_res_idx as in ``fabric_fused_batch``; pin_src: (N,) node → state
    slot ([regs | io | mem | zero] layout); reg_src: (R,) node feeding
    each register; mem_in: (M,); io_out: (n_io,) observed port nodes.
    Returns (B, T, n_io) observations, bit-identical to scanning
    ``fabric_fused_batch`` cycle by cycle. ``interpret=None`` resolves
    from the backend per call."""
    if interpret is None:
        interpret = _default_interpret()
    return _fabric_fused_run_jit(sel, ext, depths, op, const, imm_mask,
                                 imm_val, src, keep, pin_mask, pin_src,
                                 pe_in, pe_res_idx, reg_src, mem_in,
                                 io_out, n_reg, n_io, n_mem, max_depth,
                                 chunk, word, interpret)


@functools.partial(jax.jit,
                   static_argnames=("n_reg", "n_io", "n_mem", "max_depth",
                                    "chunk", "word", "interpret"))
def _fabric_fused_run_jit(sel: jnp.ndarray, ext: jnp.ndarray,
                          depths: jnp.ndarray, op: jnp.ndarray,
                          const: jnp.ndarray, imm_mask: jnp.ndarray,
                          imm_val: jnp.ndarray, src: jnp.ndarray,
                          keep: jnp.ndarray, pin_mask: jnp.ndarray,
                          pin_src: jnp.ndarray, pe_in: jnp.ndarray,
                          pe_res_idx: jnp.ndarray, reg_src: jnp.ndarray,
                          mem_in: jnp.ndarray, io_out: jnp.ndarray,
                          n_reg: int, n_io: int, n_mem: int,
                          max_depth: int, chunk: int, word: int,
                          interpret: bool) -> jnp.ndarray:
    b, n = sel.shape
    t_len = ext.shape[1]
    f = src.shape[1]
    p = pe_in.shape[0]
    bb = FUSED_LANES
    tc = max(1, chunk)
    b_pad = pl.cdiv(max(b, 1), bb) * bb
    t_pad = pl.cdiv(max(t_len, 1), tc) * tc
    n_pad = pl.cdiv(n + 1, 128) * 128               # index N = zero sentinel
    io_p = pl.cdiv(max(n_io, 1), 128) * 128
    s_len = n_reg + n_io + n_mem + 1                # trailing zero slot
    s_pad = pl.cdiv(s_len, 128) * 128
    r_p = pl.cdiv(max(n_reg, 1), 128) * 128
    m_p = pl.cdiv(max(n_mem, 1), 128) * 128
    db, dn = b_pad - b, n_pad - n
    sel_p = jnp.pad(sel, ((0, db), (0, dn)))
    ext_p = jnp.pad(ext.astype(jnp.int32),
                    ((0, db), (0, t_pad - t_len), (0, io_p - n_io)))
    depths_p = jnp.pad(depths.astype(jnp.int32), (0, db))
    op_p = jnp.pad(op, ((0, db), (0, 0)))
    const_p = jnp.pad(const, ((0, db), (0, 0)))
    imm_mask_p = jnp.pad(imm_mask, ((0, db), (0, 0), (0, 0)))
    imm_val_p = jnp.pad(imm_val, ((0, db), (0, 0), (0, 0)))
    src_p = jnp.pad(src, ((0, dn), (0, 0)), constant_values=n)
    keep_p = jnp.pad(keep, (0, dn), constant_values=1)
    pin_mask_p = jnp.pad(pin_mask, (0, dn))
    pin_src_p = jnp.pad(pin_src, (0, dn), constant_values=s_len - 1)
    pe_res_idx_p = jnp.pad(pe_res_idx, (0, dn), constant_values=2 * p)
    # node-space sentinel n: vals[n] is 0 (padded region holds zeros)
    reg_src_p = jnp.pad(reg_src, (0, r_p - reg_src.shape[0]),
                        constant_values=n)
    mem_in_p = jnp.pad(mem_in, (0, m_p - mem_in.shape[0]),
                       constant_values=n)
    io_out_p = jnp.pad(io_out, (0, io_p - io_out.shape[0]),
                       constant_values=n)
    grid = (b_pad // bb, t_pad // tc)
    obs, _state = pl.pallas_call(
        functools.partial(_fused_run_kernel, max_depth=max_depth,
                          word=word, chunk=tc, n_reg=n_reg, n_io=n_io,
                          n_mem=n_mem),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb,), lambda i, j: (i,)),            # depths
            pl.BlockSpec((bb, n_pad), lambda i, j: (i, 0)),    # sel
            pl.BlockSpec((bb, p), lambda i, j: (i, 0)),        # op
            pl.BlockSpec((bb, p), lambda i, j: (i, 0)),        # const
            pl.BlockSpec((bb, p, 4), lambda i, j: (i, 0, 0)),  # imm_mask
            pl.BlockSpec((bb, p, 4), lambda i, j: (i, 0, 0)),  # imm_val
            pl.BlockSpec((bb, tc, io_p), lambda i, j: (i, j, 0)),  # ext
            pl.BlockSpec((n_pad, f), lambda i, j: (0, 0)),     # src
            pl.BlockSpec((n_pad,), lambda i, j: (0,)),         # keep
            pl.BlockSpec((n_pad,), lambda i, j: (0,)),         # pin_mask
            pl.BlockSpec((n_pad,), lambda i, j: (0,)),         # pin_src
            pl.BlockSpec((p, 4), lambda i, j: (0, 0)),         # pe_in
            pl.BlockSpec((n_pad,), lambda i, j: (0,)),         # pe_res_idx
            pl.BlockSpec((r_p,), lambda i, j: (0,)),           # reg_src
            pl.BlockSpec((m_p,), lambda i, j: (0,)),           # mem_in
            pl.BlockSpec((io_p,), lambda i, j: (0,)),          # io_out
        ],
        out_specs=[
            pl.BlockSpec((bb, tc, io_p), lambda i, j: (i, j, 0)),  # obs
            pl.BlockSpec((bb, s_pad), lambda i, j: (i, 0)),    # state
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, t_pad, io_p), jnp.int32),
            jax.ShapeDtypeStruct((b_pad, s_pad), jnp.int32),
        ],
        interpret=interpret,
    )(depths_p, sel_p, op_p, const_p, imm_mask_p, imm_val_p, ext_p,
      src_p, keep_p, pin_mask_p, pin_src_p, jnp.asarray(pe_in),
      pe_res_idx_p, reg_src_p, mem_in_p, io_out_p)
    return obs[:b, :t_len, :n_io]
