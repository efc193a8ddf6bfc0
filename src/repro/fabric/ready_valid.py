"""Ready-valid (statically configured NoC) backend — Canal §3.3,
Figs. 5–6.

Same IR, different lowering:

* **valid** flows with the data: identical gather network, 1-bit values.
* **ready** flows *backwards*; at every fan-in point the joining logic
  reuses the data mux's one-hot select (Fig. 5): the ready contribution of
  consumer ``d`` to producer ``n`` is ``R(d) OR (sel(d) != index(n))`` —
  i.e. high when ``d`` is ready *or* the route through ``d`` does not use
  ``n``. Producer ready is the AND over all consumers. No LUTs.
* **registers become FIFOs**. Two modes (Fig. 6 / Fig. 8):
  - ``full``: every register node is a depth-2 FIFO with *registered*
    occupancy-based ready (cuts the control timing path; +54% SB area);
  - ``split``: each register keeps its single slot, and the *chain* of two
    adjacent single-slot stages behaves as one depth-2 FIFO. Ready is
    pop-aware (``~occ OR popping``), i.e. a combinational control chain —
    exactly the paper's noted drawback (unregistered control at tile
    boundaries) in exchange for +32% instead of +54% area.

The step function is a synchronous two-phase evaluation per cycle:
forward fixpoint sweeps for (data, valid), backward fixpoint sweeps for
ready, then FIFO push/pop state update. Everything is jit-able.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.graph import Interconnect
from repro.core.lowering import FabricModule


class RVFabric(FabricModule):
    """Hybrid ready-valid interconnect functional model."""

    def __init__(self, ic: Interconnect, fifo_mode: str = "split"):
        if fifo_mode not in ("full", "split"):
            raise ValueError("fifo_mode must be 'full' or 'split'")
        self.fifo_mode = fifo_mode
        self.fifo_depth = 2 if fifo_mode == "full" else 1
        super().__init__(ic)
        self._build_reverse_tables()

    # ------------------------------------------------------------------ build
    def _build_reverse_tables(self) -> None:
        a = self.arrays
        n = a.num_nodes
        cons_lists: List[List[Tuple[int, int]]] = [[] for _ in range(n)]
        for i, node in enumerate(self.nodes):
            for j, srcn in enumerate(node.fan_in):
                cons_lists[self.node_id[srcn]].append((i, j))
        max_c = max(1, max(len(c) for c in cons_lists))
        # consumer node id, padded with n (sentinel: always-ready consumer)
        cons = np.full((n, max_c), n, dtype=np.int32)
        cons_idx = np.zeros((n, max_c), dtype=np.int32)
        for i, lst in enumerate(cons_lists):
            for k, (ci, cj) in enumerate(lst):
                cons[i, k] = ci
                cons_idx[i, k] = cj
        self.cons = cons
        self.cons_idx = cons_idx
        self.max_cons = max_c
        self.is_reg_arr = a.is_reg.copy()
        # map node id -> register slot index
        self.reg_slot = np.full(n, -1, dtype=np.int32)
        for r, i in enumerate(a.reg_ids):
            self.reg_slot[i] = r
        # PE handshake: outputs' ready joins into all inputs
        # (handled via dedicated pe pass below)

    # -------------------------------------------------------------- interface
    def init_state(self) -> Dict[str, jnp.ndarray]:
        r = len(self.arrays.reg_ids)
        return {
            "slots": jnp.zeros((r, 2), dtype=jnp.int32),   # FIFO storage
            "occ": jnp.zeros((r,), dtype=jnp.int32),       # occupancy
            "mem": jnp.zeros(max(self.num_mem, 1), dtype=jnp.int32),
        }

    # ------------------------------------------------------------- evaluation
    def _forward(self, sel: jnp.ndarray, data0: jnp.ndarray,
                 valid0: jnp.ndarray, pin_data: jnp.ndarray,
                 pin_valid: jnp.ndarray, pin_mask: jnp.ndarray,
                 pe_cfg: Dict[str, jnp.ndarray],
                 depth: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Fixpoint forward sweeps for (data, valid). ``pin_*`` hold register
        outputs / external inputs fixed every sweep."""
        a = self.arrays
        src = jnp.asarray(a.src)
        keep = jnp.asarray(~a.is_driven)

        def body(_, dv):
            d, v = dv
            d_ext = jnp.concatenate([d, jnp.zeros(1, jnp.int32)])
            v_ext = jnp.concatenate([v, jnp.zeros(1, jnp.int32)])
            src_sel = jnp.take_along_axis(src, sel[:, None], axis=1)[:, 0]
            nd = jnp.where(keep, d, d_ext[src_sel])
            nv = jnp.where(keep, v, v_ext[src_sel])
            nd = jnp.where(pin_mask, pin_data, nd)
            nv = jnp.where(pin_mask, pin_valid, nv)
            nd = self._eval_pes(nd, pe_cfg)
            nv = self._eval_pe_valid(nv)
            return nd, nv

        return jax.lax.fori_loop(0, depth, body, (data0, valid0))

    def _eval_pe_valid(self, valid: jnp.ndarray) -> jnp.ndarray:
        """PE fires when all its (connected) inputs are valid."""
        if self.num_pe == 0:
            return valid
        v_ext = jnp.concatenate([valid, jnp.ones(1, jnp.int32)])
        ins = v_ext[jnp.asarray(self.pe_in)]              # (n_pe, 4)
        fire = jnp.min(ins[:, :2], axis=1)                # binary AND of a,b
        out_ids = jnp.asarray(self.pe_out)
        valid = valid.at[out_ids[:, 0]].set(fire)
        if self.pe_out.shape[1] > 1:
            valid = valid.at[out_ids[:, 1]].set(fire)
        return valid

    def _backward(self, sel: jnp.ndarray, ready0: jnp.ndarray,
                  reg_ready: jnp.ndarray, sink_ready: jnp.ndarray,
                  sink_mask: jnp.ndarray, depth: int) -> jnp.ndarray:
        """Fixpoint backward sweeps for ready with one-hot join (Fig. 5).

        reg_ready: per-node pinned ready for register nodes (computed from
        occupancy; in split mode it still participates in the chain via the
        pop-aware term added by the caller). sink_mask pins external sinks.
        """
        a = self.arrays
        cons = jnp.asarray(self.cons)
        cons_idx = jnp.asarray(self.cons_idx)
        is_reg = jnp.asarray(a.is_reg)
        has_cons = jnp.asarray((self.cons < a.num_nodes).any(axis=1))

        def body(_, r):
            r_ext = jnp.concatenate([r, jnp.ones(1, jnp.int32)])
            cr = r_ext[cons]                        # (N, C) consumer ready
            csel = jnp.concatenate([sel, jnp.zeros(1, jnp.int32)])[cons]
            used = (csel == cons_idx) & (cons < a.num_nodes)
            # Fig. 5: ready_j OR not-used_j, ANDed across consumers
            contrib = jnp.where(used, cr, 1)
            nr = jnp.min(contrib, axis=1)
            nr = jnp.where(has_cons, nr, 1)
            nr = jnp.where(is_reg, reg_ready, nr)
            nr = jnp.where(sink_mask, sink_ready, nr)
            return nr

        return jax.lax.fori_loop(0, depth, body, ready0)

    def step(self, state: Dict[str, jnp.ndarray], ext_in: jnp.ndarray,
             ext_valid: jnp.ndarray, config: jnp.ndarray,
             pe_cfg: Optional[Dict[str, jnp.ndarray]] = None,
             ext_sink_ready: Optional[jnp.ndarray] = None,
             depth: int = 24
             ) -> Tuple[Dict[str, jnp.ndarray],
                        Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
        """One NoC cycle. Returns (state', (io_data, io_valid, io_in_ready)).

        io_in_ready is the backpressure the fabric presents to external
        producers (at io_out ports).
        """
        if pe_cfg is None:
            pe_cfg = self.default_pe_cfg()
        a = self.arrays
        n = a.num_nodes
        sel = self._selects(config)
        occ = state["occ"]
        slots = state["slots"]
        r_ids = jnp.asarray(a.reg_ids) if len(a.reg_ids) else None

        # ---------------- forward: data & valid --------------------------
        pin_mask = jnp.zeros(n, dtype=bool)
        pin_data = jnp.zeros(n, dtype=jnp.int32)
        pin_valid = jnp.zeros(n, dtype=jnp.int32)
        if r_ids is not None:
            head = slots[:, 0]
            pin_mask = pin_mask.at[r_ids].set(True)
            pin_data = pin_data.at[r_ids].set(head)
            pin_valid = pin_valid.at[r_ids].set((occ > 0).astype(jnp.int32))
        if self.num_io:
            ion = jnp.asarray(self.io_in_nodes)
            pin_mask = pin_mask.at[ion].set(True)
            pin_data = pin_data.at[ion].set(ext_in.astype(jnp.int32))
            pin_valid = pin_valid.at[ion].set(ext_valid.astype(jnp.int32))
        data0 = jnp.where(pin_mask, pin_data, 0)
        valid0 = jnp.where(pin_mask, pin_valid, 0)
        data, valid = self._forward(sel, data0, valid0, pin_data, pin_valid,
                                    pin_mask, pe_cfg, depth)

        # ---------------- backward: ready --------------------------------
        sink_mask = jnp.zeros(n, dtype=bool)
        sink_ready = jnp.ones(n, dtype=jnp.int32)
        if self.num_io:
            ioo = jnp.asarray(self.io_out_nodes)
            sink_mask = sink_mask.at[ioo].set(True)
            if ext_sink_ready is not None:
                sink_ready = sink_ready.at[ioo].set(
                    ext_sink_ready.astype(jnp.int32))
        ready0 = jnp.ones(n, dtype=jnp.int32)
        if self.fifo_mode == "full":
            # registered control: ready depends only on occupancy (< 2)
            reg_ready_vec = (occ < 2).astype(jnp.int32)
            reg_ready = jnp.ones(n, jnp.int32)
            if r_ids is not None:
                reg_ready = reg_ready.at[r_ids].set(reg_ready_vec)
            ready = self._backward(sel, ready0, reg_ready, sink_ready,
                                   sink_mask, depth)
        else:
            # split mode: pop-aware combinational control chain. Iterate the
            # backward sweep with reg_ready recomputed from downstream ready
            # (the unregistered tile-boundary control path, Fig. 6).
            def rbody(_, r):
                pop = self._reg_pop(r, sel, occ)
                reg_ready_vec = jnp.where(occ < 1, 1, pop).astype(jnp.int32)
                reg_ready = jnp.ones(n, jnp.int32)
                rr = reg_ready.at[r_ids].set(reg_ready_vec) \
                    if r_ids is not None else reg_ready
                return self._backward(sel, r, rr, sink_ready, sink_mask, 1)

            ready = jax.lax.fori_loop(0, depth, rbody, ready0)

        # ---------------- sequential update -------------------------------
        new_state = dict(state)
        if r_ids is not None:
            pop = self._reg_pop(ready, sel, occ) * (occ > 0).astype(jnp.int32)
            # the value at the register's input after the forward pass
            d_ext = jnp.concatenate([data, jnp.zeros(1, jnp.int32)])
            v_ext = jnp.concatenate([valid, jnp.zeros(1, jnp.int32)])
            in_data = d_ext[jnp.asarray(a.reg_src)]
            in_valid = v_ext[jnp.asarray(a.reg_src)]
            r_ext = jnp.concatenate([ready, jnp.ones(1, jnp.int32)])
            my_ready = r_ext[r_ids]
            push = in_valid * my_ready
            occ_after_pop = occ - pop
            # shift-down FIFO: on pop, slot1 -> slot0
            slots = jnp.where((pop > 0)[:, None],
                              jnp.stack([slots[:, 1],
                                         jnp.zeros_like(slots[:, 1])], 1),
                              slots)
            write_idx = jnp.clip(occ_after_pop, 0, 1)
            do_push = (push > 0) & (occ_after_pop < self.fifo_depth)
            slots = jnp.where(
                do_push[:, None],
                slots.at[jnp.arange(slots.shape[0]), write_idx]
                     .set(in_data, mode="drop"),
                slots)
            occ = occ_after_pop + do_push.astype(jnp.int32)
            new_state["slots"] = slots
            new_state["occ"] = occ

        io_data = (data[jnp.asarray(self.io_out_nodes)]
                   if self.num_io else jnp.zeros(0, jnp.int32))
        io_valid = (valid[jnp.asarray(self.io_out_nodes)]
                    if self.num_io else jnp.zeros(0, jnp.int32))
        io_ready = (ready[jnp.asarray(self.io_in_nodes)]
                    if self.num_io else jnp.zeros(0, jnp.int32))
        return new_state, (io_data, io_valid, io_ready)

    def _reg_pop(self, ready: jnp.ndarray, sel: jnp.ndarray,
                 occ: jnp.ndarray) -> jnp.ndarray:
        """Whether each register's head is consumed this cycle: its (single)
        consumer mux selects it AND that consumer is ready."""
        a = self.arrays
        if not len(a.reg_ids):
            return jnp.zeros(0, jnp.int32)
        cons = jnp.asarray(self.cons)[jnp.asarray(a.reg_ids)]      # (R, C)
        cons_idx = jnp.asarray(self.cons_idx)[jnp.asarray(a.reg_ids)]
        r_ext = jnp.concatenate([ready, jnp.ones(1, jnp.int32)])
        s_ext = jnp.concatenate([sel, jnp.zeros(1, jnp.int32)])
        used = (s_ext[cons] == cons_idx) & (cons < a.num_nodes)
        consumed = jnp.where(used, r_ext[cons], 1)
        return jnp.min(consumed, axis=1).astype(jnp.int32)

    def run_stream(self, config: jnp.ndarray, ext_data: jnp.ndarray,
                   ext_valid: jnp.ndarray,
                   ext_sink_ready: Optional[jnp.ndarray] = None,
                   pe_cfg: Optional[Dict[str, jnp.ndarray]] = None,
                   depth: int = 24):
        """Run T cycles of the NoC. ext_data/ext_valid: (T, num_io).
        ext_sink_ready: (T, num_io) backpressure from external consumers."""
        state = self.init_state()
        if ext_sink_ready is None:
            ext_sink_ready = jnp.ones_like(ext_valid)

        def scan_fn(st, xs):
            d, v, r = xs
            st, out = self.step(st, d, v, config, pe_cfg,
                                ext_sink_ready=r, depth=depth)
            return st, out

        _, outs = jax.lax.scan(scan_fn, state,
                               (ext_data, ext_valid, ext_sink_ready))
        return outs


    def run_with_sources(self, config: jnp.ndarray, streams: jnp.ndarray,
                         stream_lens: jnp.ndarray, sink_ready: jnp.ndarray,
                         pe_cfg: Optional[Dict[str, jnp.ndarray]] = None,
                         depth: int = 24):
        """Run with handshake-respecting sources: each IO presents
        ``streams[ptr, io]`` and only advances its pointer when the fabric
        accepts (valid & ready). This is the latency-insensitive testbench
        the hybrid interconnect is designed for.

        streams: (T, num_io) data; stream_lens: (num_io,) items per source;
        sink_ready: (T, num_io) external consumer backpressure.
        Returns (io_data, io_valid, accepted_mask) each (T, num_io).
        """
        state = self.init_state()
        t_max = streams.shape[0]
        n_io = self.num_io
        io_arange = jnp.arange(n_io)

        def scan_fn(carry, xs):
            st, ptr = carry
            s_ready = xs
            d = streams[jnp.clip(ptr, 0, t_max - 1), io_arange]
            v = (ptr < stream_lens).astype(jnp.int32)
            st, (od, ov, orr) = self.step(st, d, v, config, pe_cfg,
                                          ext_sink_ready=s_ready,
                                          depth=depth)
            ptr = ptr + v * orr
            accepted = ov * s_ready
            return (st, ptr), (od, ov, accepted)

        (_, ptr), outs = jax.lax.scan(scan_fn, (state, jnp.zeros(n_io,
                                                                 jnp.int32)),
                                      sink_ready)
        return outs


def compile_ready_valid(ic: Interconnect,
                        fifo_mode: str = "split") -> RVFabric:
    """Ready-valid backend entry point (the hybrid interconnect, §3.3)."""
    return RVFabric(ic, fifo_mode=fifo_mode)
