"""Static-interconnect hardware backend (Canal §3.3).

Lowers the graph IR into a *functional JAX model* of the fabric instead of
magma RTL. The paper's three lowering rules are applied mechanically:

1. nodes with hardware attributes (cores) generate the specified hardware —
   here, a vectorized functional model of the PE/MEM/IO cores;
2. directed edges become wires — here, entries in a gather table;
3. nodes with multiple incoming edges become multiplexers — here,
   config-indexed selects into the gather table.

Because the structural graph contains *potential* combinational cycles
(register-bypass muxes), the fabric evaluates each cycle by fixpoint
sweeps: one sweep propagates every node's value one combinational level.
A legal configuration's active network is acyclic, so ``depth`` sweeps
(≥ longest configured combinational path) reach the fixed point.

Two engines evaluate it. The per-config ``step``/``run`` sweeps every
node, one configuration at a time: the serial reference of the tests and
the engine of ``RVFabric``. The batched ``step_batch``/``run_batch`` runs
the chain engine (``repro.kernels.fabric_chain``): it collapses every
chain of selected inputs to its root once a batch, so a sweep evaluates
only the 4P PE input slots. It masks each configuration to its own
combinational depth and shards the batch axis across devices. Its test
oracle is the N-wide sweep ``repro.kernels.ref.fabric_fused_batch_ref``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from repro.kernels import fabric_chain
from repro.kernels.fabric_chain import PE_OPS, pe_alu_candidates

from .graph import Interconnect, Node, NodeKind
from .tiles import IOCore, MemCore, PECore, WORD

assert PECore.OPS == PE_OPS, \
    "fabric_chain.PE_OPS must mirror PECore.OPS (shared PE ALU datapath)"
PE_OP_IDS = {op: i for i, op in enumerate(PECore.OPS)}

DepthSpec = Union[int, np.ndarray, jnp.ndarray]


def _delayed_as_immediates(reg_mask, imm_mask, imm_val, pe_regs):
    """Fold delayed PE ports into the immediates: within a cycle a
    delayed input is a constant, the value its port captured at the end
    of the last cycle (``pe_regs``). The fixpoint then needs no change;
    the caller captures the ports' values after it."""
    delayed = reg_mask > 0
    return (jnp.where(delayed, 1, imm_mask),
            jnp.where(delayed, pe_regs, imm_val))


@dataclass
class ConfigSlot:
    node_id: int
    fanin: int
    num_bits: int
    # bitstream address (tile-feature-register, see repro.core.bitstream)
    x: int
    y: int
    feature: str
    reg_index: int


@dataclass
class FabricArrays:
    """Dense tables driving the sweep evaluation. All numpy on the host;
    converted to jnp at jit boundaries."""

    num_nodes: int
    max_fanin: int
    src: np.ndarray           # (N, F) int32, padded with N (zero sentinel)
    fanin_count: np.ndarray   # (N,) int32
    config_slot: np.ndarray   # (N,) int32, -1 when unconfigured
    is_reg: np.ndarray        # (N,) bool
    is_driven: np.ndarray     # (N,) bool: updated by sweeps
    reg_ids: np.ndarray       # (R,) node ids of registers
    reg_src: np.ndarray       # (R,) node id feeding each register
    num_config: int


class FabricModule:
    """Functional model of the generated interconnect + cores.

    ``step(state, ext_in, config, pe_cfg)`` advances one fabric clock cycle;
    everything is jit/vmap friendly. Node values are int32 words masked to
    the layer bit width.
    """

    def __init__(self, ic: Interconnect):
        self.ic = ic
        self.nodes: List[Node] = list(ic.nodes())
        self.node_id: Dict[Node, int] = {n: i for i, n in
                                         enumerate(self.nodes)}
        self.config_slots: List[ConfigSlot] = []
        #: run_batch's program, jitted once: JAX keys it on the static
        #: loop bound (an eagerly called scan would be traced and
        #: compiled again on every call)
        self._run_batch_jit = jax.jit(self._run_batch_local,
                                      static_argnums=(4,))
        #: its sharded form, one per device tuple
        self._sharded_programs: Dict[Tuple, object] = {}
        self._build_tables()
        self._build_cores()

    # ------------------------------------------------------------------ build
    def _feature_of(self, node: Node) -> str:
        if node.kind == NodeKind.PORT:
            return f"CB_{node.port_name}"
        return "SB"

    def _build_tables(self) -> None:
        n = len(self.nodes)
        fanins = [len(node.fan_in) for node in self.nodes]
        max_f = max(1, max(fanins, default=1))
        src = np.full((n, max_f), n, dtype=np.int32)   # sentinel = n
        fanin_count = np.zeros(n, dtype=np.int32)
        config_slot = np.full(n, -1, dtype=np.int32)
        is_reg = np.zeros(n, dtype=bool)
        is_driven = np.zeros(n, dtype=bool)

        # per-(tile, feature) register index counter for bitstream addressing
        feat_counter: Dict[Tuple[int, int, str], int] = {}

        for i, node in enumerate(self.nodes):
            fi = len(node.fan_in)
            fanin_count[i] = fi
            for j, s in enumerate(node.fan_in):
                src[i, j] = self.node_id[s]
            if node.kind == NodeKind.REGISTER:
                is_reg[i] = True
                continue
            if fi >= 1:
                is_driven[i] = True
            if fi > 1:
                key = (node.x, node.y, self._feature_of(node))
                idx = feat_counter.get(key, 0)
                feat_counter[key] = idx + 1
                config_slot[i] = len(self.config_slots)
                self.config_slots.append(ConfigSlot(
                    node_id=i, fanin=fi,
                    num_bits=int(np.ceil(np.log2(fi))),
                    x=node.x, y=node.y, feature=key[2], reg_index=idx))

        reg_ids = np.array([i for i, node in enumerate(self.nodes)
                            if node.kind == NodeKind.REGISTER],
                           dtype=np.int32)
        reg_src = np.array([src[i, 0] for i in reg_ids], dtype=np.int32)

        self.arrays = FabricArrays(
            num_nodes=n, max_fanin=max_f, src=src, fanin_count=fanin_count,
            config_slot=config_slot, is_reg=is_reg, is_driven=is_driven,
            reg_ids=reg_ids, reg_src=reg_src,
            num_config=len(self.config_slots))
        self.width_mask = np.array(
            [(1 << node.width) - 1 for node in self.nodes] + [0],
            dtype=np.int32)

    def _build_cores(self) -> None:
        """Vectorized core models: PEs and IOs (MEM modeled as delay reg)."""
        pe_in: List[List[int]] = []     # (n_pe, 4) input port node ids
        pe_out: List[List[int]] = []    # (n_pe, 2) output port node ids
        self.pe_coords: List[Tuple[int, int]] = []
        io_in_nodes: List[int] = []     # io_out ports (externally driven)
        io_out_nodes: List[int] = []    # io_in ports (externally observed)
        self.io_coords: List[Tuple[int, int]] = []
        mem_in: List[int] = []
        mem_out: List[int] = []

        sentinel = self.arrays.num_nodes
        seen = set()
        for g in self.ic.graphs.values():
            for (x, y), tile in sorted(g.tiles.items()):
                if tile.core is None or (x, y) in seen:
                    continue
                seen.add((x, y))
                core = tile.core
                if isinstance(core, PECore):
                    ins = [self.node_id[tile.get_port(f"data{i}")]
                           for i in range(core.num_inputs)]
                    ins += [sentinel] * (4 - len(ins))
                    outs = [self.node_id[tile.get_port(f"res{i}")]
                            for i in range(core.num_outputs)]
                    pe_in.append(ins[:4])
                    pe_out.append(outs)
                    self.pe_coords.append((x, y))
                elif isinstance(core, IOCore):
                    io_in_nodes.append(self.node_id[tile.get_port("io_out")])
                    io_out_nodes.append(self.node_id[tile.get_port("io_in")])
                    self.io_coords.append((x, y))
                elif isinstance(core, MemCore):
                    mem_in.append(self.node_id[tile.get_port("wdata")])
                    mem_out.append(self.node_id[tile.get_port("rdata")])

        self.pe_in = np.array(pe_in, dtype=np.int32).reshape(-1, 4)
        self.pe_out = (np.array(pe_out, dtype=np.int32)
                       if pe_out else np.zeros((0, 2), np.int32))
        self.io_in_nodes = np.array(io_in_nodes, dtype=np.int32)
        self.io_out_nodes = np.array(io_out_nodes, dtype=np.int32)
        self.mem_in = np.array(mem_in, dtype=np.int32)
        self.mem_out = np.array(mem_out, dtype=np.int32)
        self.num_pe = len(pe_in)
        self.num_io = len(io_in_nodes)
        self.num_mem = len(mem_in)
        self._build_fused_tables()

    def _build_fused_tables(self) -> None:
        """Node/PE tables for the batched engine and its sweep oracle:
        hold-flags, pin mask, sentinel-padded PE inputs and the
        scatter-free node -> PE-result index map."""
        a = self.arrays
        n = a.num_nodes
        p = max(self.num_pe, 1)
        pe_in = np.full((p, 4), n, dtype=np.int32)
        if self.num_pe:
            pe_in[:self.num_pe] = self.pe_in
        pe_res_idx = np.full(n, 2 * p, dtype=np.int32)
        for k in range(self.num_pe):
            for col in range(self.pe_out.shape[1]):
                pe_res_idx[self.pe_out[k, col]] = 2 * k + col
        pin_mask = np.zeros(n, dtype=np.int32)
        if len(a.reg_ids):
            pin_mask[a.reg_ids] = 1
        if self.num_io:
            pin_mask[self.io_in_nodes] = 1
        if self.num_mem:
            pin_mask[self.mem_out] = 1
        self.fused_tables = {
            "keep": (~a.is_driven).astype(np.int32),
            "pin_mask": pin_mask,
            "pe_in": pe_in,
            "pe_res_idx": pe_res_idx,
            "num_pe_slots": p,
            # the nodes a cycle reads out: the PE inputs (their delay
            # registers), the register sources, the memory inputs and the
            # observed IO ports
            "read": np.concatenate([pe_in.ravel(), a.reg_src, self.mem_in,
                                    self.io_out_nodes]).astype(np.int32),
        }
        self._chain_consts: Optional[Dict[str, np.ndarray]] = None

    def chain_consts(self) -> Dict[str, np.ndarray]:
        """Node tables for the chain engine (``kernels/fabric_chain.py``),
        each (N + 1,) with the sentinel: ``root`` (held, pinned and PE
        output nodes), ``res`` (``pe_res_idx``) and ``pin_src``, the node
        -> pinned-state map. The pinned state is ``[regs | ext io | mem |
        zero]``; every unpinned node points at the trailing zero."""
        if self._chain_consts is None:
            a, t = self.arrays, self.fused_tables
            n, p = a.num_nodes, t["num_pe_slots"]
            n_reg = len(a.reg_ids)
            s_zero = n_reg + self.num_io + self.num_mem
            pin_src = np.full(n + 1, s_zero, dtype=np.int32)
            pin_src[a.reg_ids] = np.arange(n_reg, dtype=np.int32)
            pin_src[self.io_in_nodes] = n_reg + np.arange(
                self.num_io, dtype=np.int32)
            pin_src[self.mem_out] = n_reg + self.num_io + np.arange(
                self.num_mem, dtype=np.int32)
            outs = self.pe_out.ravel()
            if np.any(a.is_driven[outs]) or np.any(t["pin_mask"][outs]):
                raise NotImplementedError(
                    "the chain engine needs every PE output node undriven "
                    "and unpinned")
            res = np.append(t["pe_res_idx"], 2 * p).astype(np.int32)
            self._chain_consts = {
                "root": np.append(
                    (t["keep"] | t["pin_mask"]) | (res[:n] < 2 * p),
                    1).astype(np.int32),
                "res": res,
                "pin_src": pin_src,
            }
        return self._chain_consts

    # -------------------------------------------------------------- interface
    @property
    def num_config(self) -> int:
        return self.arrays.num_config

    @property
    def slots_per_sweep(self) -> int:
        """Node values the batched engine gathers per lane per sweep: the
        4P PE input slots."""
        return 4 * self.fused_tables["num_pe_slots"]

    def init_state(self) -> Dict[str, jnp.ndarray]:
        """Registers, memories and the PE input registers (``pe_regs``,
        (P, 4): what each PE input port carried last cycle)."""
        return {
            "regs": jnp.zeros(len(self.arrays.reg_ids), dtype=jnp.int32),
            "mem": jnp.zeros(max(self.num_mem, 1), dtype=jnp.int32),
            "pe_regs": jnp.zeros((self.fused_tables["num_pe_slots"], 4),
                                 dtype=jnp.int32),
        }

    def init_state_batch(self, batch: int) -> Dict[str, jnp.ndarray]:
        """State for ``batch`` independent configurations (leading B dim)."""
        return {k: jnp.broadcast_to(v, (batch,) + v.shape)
                for k, v in self.init_state().items()}

    def default_pe_cfg(self) -> Dict[str, jnp.ndarray]:
        n = max(self.num_pe, 1)
        return {
            "op": jnp.full((n,), PE_OP_IDS["add"], dtype=jnp.int32),
            "const": jnp.zeros((n,), dtype=jnp.int32),
            # per-port packed-constant immediates (packing stage, §3.4)
            "imm_mask": jnp.zeros((n, 4), dtype=jnp.int32),
            "imm_val": jnp.zeros((n, 4), dtype=jnp.int32),
            # per-port delay mode: the input reads last cycle's value
            # (Amber's PE input register; packed app registers)
            "reg_mask": jnp.zeros((n, 4), dtype=jnp.int32),
        }

    def default_pe_cfg_batch(self, batch: int) -> Dict[str, jnp.ndarray]:
        one = self.default_pe_cfg()
        return {k: jnp.broadcast_to(v, (batch,) + v.shape)
                for k, v in one.items()}

    # ------------------------------------------------------------- evaluation
    def _selects(self, config: jnp.ndarray) -> jnp.ndarray:
        """Per-node mux select: config value clipped to fan-in, 0 default."""
        a = self.arrays
        slot = jnp.asarray(a.config_slot)
        if a.num_config == 0:
            return jnp.zeros(a.num_nodes, dtype=jnp.int32)
        sel = jnp.where(slot >= 0,
                        config[jnp.clip(slot, 0, a.num_config - 1)],
                        0)
        return jnp.clip(sel, 0, jnp.maximum(jnp.asarray(a.fanin_count) - 1,
                                            0))

    def _sweep(self, vals_ext: jnp.ndarray, sel: jnp.ndarray) -> jnp.ndarray:
        """One combinational propagation sweep of the per-config engine.
        vals_ext has the zero sentinel appended (length N+1); returns (N,).
        """
        a = self.arrays
        src_sel = jnp.take_along_axis(
            jnp.asarray(a.src), sel[:, None], axis=1)[:, 0]
        new = vals_ext[src_sel]
        keep = jnp.asarray(~a.is_driven)
        return jnp.where(keep, vals_ext[:-1], new) \
                  .astype(jnp.int32)

    def _eval_pes(self, vals: jnp.ndarray,
                  pe_cfg: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """vals is the (N,) vector; sentinel-padded PE inputs read 0 via the
        extended gather below."""
        if self.num_pe == 0:
            return vals
        vals_ext = jnp.concatenate([vals, jnp.zeros(1, jnp.int32)])
        ins = vals_ext[jnp.asarray(self.pe_in)]      # (n_pe, 4)
        if "imm_mask" in pe_cfg:
            ins = jnp.where(pe_cfg["imm_mask"][:self.num_pe] > 0,
                            pe_cfg["imm_val"][:self.num_pe], ins)
        a, b, c = ins[:, 0], ins[:, 1], ins[:, 2]
        op = pe_cfg["op"][:self.num_pe]
        const = pe_cfg["const"][:self.num_pe]
        candidates = pe_alu_candidates(a, b, c, const)   # (n_ops, n_pe)
        res0 = jnp.take_along_axis(candidates, op[None, :], axis=0)[0]
        res0 = res0 & WORD
        res1 = a & WORD                        # second output: pass-through
        out_ids = jnp.asarray(self.pe_out)
        vals = vals.at[out_ids[:, 0]].set(res0)
        if self.pe_out.shape[1] > 1:
            vals = vals.at[out_ids[:, 1]].set(res1)
        return vals

    def step(self, state: Dict[str, jnp.ndarray], ext_in: jnp.ndarray,
             config: jnp.ndarray,
             pe_cfg: Optional[Dict[str, jnp.ndarray]] = None,
             depth: int = 16) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
        """One fabric clock cycle.

        state: registers/mem/PE input registers. ext_in: (num_io,) values
        driven onto io_out ports. config: (num_config,) mux selects.
        Returns (state', io_out observations). ``depth`` = fixpoint
        sweeps (≥ longest configured combinational chain).
        """
        if pe_cfg is None:
            pe_cfg = self.default_pe_cfg()
        a = self.arrays
        sel = self._selects(config)
        if "reg_mask" in pe_cfg:
            zeros = jnp.zeros_like(pe_cfg["reg_mask"])
            imm_mask, imm_val = _delayed_as_immediates(
                pe_cfg["reg_mask"], pe_cfg.get("imm_mask", zeros),
                pe_cfg.get("imm_val", zeros), state["pe_regs"])
            pe_cfg = dict(pe_cfg, imm_mask=imm_mask, imm_val=imm_val)
        # value vector with zero sentinel at index N
        vals = jnp.zeros(a.num_nodes, dtype=jnp.int32)
        if len(a.reg_ids):
            vals = vals.at[jnp.asarray(a.reg_ids)].set(state["regs"])
        if self.num_io:
            vals = vals.at[jnp.asarray(self.io_in_nodes)].set(
                ext_in.astype(jnp.int32))
        if self.num_mem:
            vals = vals.at[jnp.asarray(self.mem_out)].set(
                state["mem"][:self.num_mem])

        def body(_, v):
            v_ext = jnp.concatenate([v, jnp.zeros(1, jnp.int32)])
            v = self._sweep(v_ext, sel)
            # re-pin sources each sweep
            if len(a.reg_ids):
                v = v.at[jnp.asarray(a.reg_ids)].set(state["regs"])
            if self.num_io:
                v = v.at[jnp.asarray(self.io_in_nodes)].set(
                    ext_in.astype(jnp.int32))
            if self.num_mem:
                v = v.at[jnp.asarray(self.mem_out)].set(
                    state["mem"][:self.num_mem])
            v = self._eval_pes(v, pe_cfg)
            return v

        vals = jax.lax.fori_loop(0, depth, body, vals)
        vals_ext = jnp.concatenate([vals, jnp.zeros(1, jnp.int32)])
        new_state = dict(state)
        new_state["pe_regs"] = vals_ext[jnp.asarray(
            self.fused_tables["pe_in"])]
        if len(a.reg_ids):
            new_state["regs"] = vals_ext[jnp.asarray(a.reg_src)]
        if self.num_mem:
            new_state["mem"] = state["mem"].at[:self.num_mem].set(
                vals_ext[jnp.asarray(self.mem_in)])
        io_obs = (vals_ext[jnp.asarray(self.io_out_nodes)]
                  if self.num_io else jnp.zeros(0, jnp.int32))
        return new_state, io_obs

    @jax.named_scope("canal.emulate")
    def run(self, config: jnp.ndarray, ext_stream: jnp.ndarray,
            pe_cfg: Optional[Dict[str, jnp.ndarray]] = None,
            depth: Optional[int] = None) -> jnp.ndarray:
        """Run T cycles; ext_stream (T, num_io) -> observations (T, num_io).

        ``depth=None`` computes the per-config combinational depth from the
        configured network (host-side; requires a concrete config)."""
        if depth is None:
            depth = self.combinational_depth(np.asarray(config))
        state = self.init_state()

        def scan_fn(st, x):
            st, obs = self.step(st, x, config, pe_cfg, depth=depth)
            return st, obs

        _, out = jax.lax.scan(scan_fn, state, ext_stream)
        return out

    def _norm_depth(self, depth: DepthSpec, max_depth: Optional[int],
                    b: int) -> Tuple[jnp.ndarray, int]:
        """Normalize a depth spec into ((B,) per-lane sweep counts,
        static loop bound). A traced per-lane array needs an explicit
        ``max_depth`` (e.g. under shard_map, where the lane axis is a
        device-local slice of host-computed depths)."""
        if isinstance(depth, (int, np.integer)):
            md = int(depth) if max_depth is None else int(max_depth)
            return jnp.full((b,), int(depth), jnp.int32), md
        if max_depth is None:
            try:
                max_depth = int(np.max(np.asarray(depth))) if b else 1
            except jax.errors.TracerArrayConversionError as e:
                raise ValueError(
                    "step_batch with a traced per-lane depth array needs "
                    "an explicit static max_depth") from e
        return jnp.asarray(depth, jnp.int32), int(max_depth)

    def _norm_pe_cfg(self, pe_cfg: Dict[str, jnp.ndarray], b: int,
                     pe_regs: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        """PE program tables shaped for the batched engine: (B, P) op/const
        and (B, P, 4) immediates, P = max(num_pe, 1) slots. Delayed ports
        (``reg_mask``) become immediates holding ``pe_regs``, the (B, P,
        4) values their ports carried last cycle."""
        p = self.fused_tables["num_pe_slots"]
        npe = self.num_pe

        def pad2(x):
            x = jnp.asarray(x, jnp.int32)[:, :npe]
            return jnp.pad(x, ((0, 0), (0, p - npe)))

        def pad3(key):
            if key not in pe_cfg:
                return jnp.zeros((b, p, 4), jnp.int32)
            x = jnp.asarray(pe_cfg[key], jnp.int32)[:, :npe]
            return jnp.pad(x, ((0, 0), (0, p - npe), (0, 0)))

        imm_mask, imm_val = pad3("imm_mask"), pad3("imm_val")
        if "reg_mask" in pe_cfg:
            imm_mask, imm_val = _delayed_as_immediates(
                pad3("reg_mask"), imm_mask, imm_val, pe_regs)
        return pad2(pe_cfg["op"]), pad2(pe_cfg["const"]), imm_mask, imm_val

    def step_batch(self, state: Dict[str, jnp.ndarray], ext_in: jnp.ndarray,
                   config: jnp.ndarray,
                   pe_cfg: Optional[Dict[str, jnp.ndarray]] = None,
                   depth: DepthSpec = 16,
                   max_depth: Optional[int] = None,
                   chains: Optional[Dict[str, jnp.ndarray]] = None
                   ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
        """One fabric clock cycle for B configurations at once.

        Every argument carries a leading batch dim: state regs (B, R) /
        mem (B, M) / pe_regs (B, P, 4), ext_in (B, num_io), config (B,
        num_config), pe_cfg leaves (B, ...). Returns (state', (B, num_io)
        observations).

        ``depth`` is either a shared int or a (B,) per-configuration sweep
        count: every lane runs the static ``max_depth`` loop but freezes
        once its own count is reached, so each configuration performs
        exactly its own fixpoint. The fixpoint, PE evaluation included,
        is one call of the chain engine (``kernels/fabric_chain.py``),
        whose sweeps evaluate only the 4P PE input slots;
        ``ref.fabric_fused_batch_ref``, the N-wide sweep oracle, is its
        test reference. ``chains`` are its per-batch tables
        (``chain_tables``), built here when not given."""
        b = config.shape[0]
        if pe_cfg is None:
            pe_cfg = self.default_pe_cfg_batch(b)
        a = self.arrays
        depths, max_depth = self._norm_depth(depth, max_depth, b)
        op, const, imm_mask, imm_val = self._norm_pe_cfg(
            pe_cfg, b, state["pe_regs"])
        if chains is None:
            chains = self.chain_tables(config, max_depth)
        pin_state = jnp.concatenate(
            [state["regs"], ext_in.astype(jnp.int32),
             state["mem"][:, :self.num_mem],
             jnp.zeros((b, 1), jnp.int32)], axis=1)
        reads = fabric_chain.chain_fixpoint(
            chains, pin_state, depths, op, const, imm_mask, imm_val,
            max_depth=max_depth, word=WORD)
        p = self.fused_tables["num_pe_slots"]
        n_reg = len(a.reg_ids)
        new_state = dict(state)
        new_state["pe_regs"] = reads[:, :4 * p].reshape(b, p, 4)
        reads = reads[:, 4 * p:]
        if n_reg:
            new_state["regs"] = reads[:, :n_reg]
        if self.num_mem:
            new_state["mem"] = state["mem"].at[:, :self.num_mem].set(
                reads[:, n_reg:n_reg + self.num_mem])
        return new_state, reads[:, n_reg + self.num_mem:]

    def chain_tables(self, configs: jnp.ndarray,
                     max_depth: int) -> Dict[str, jnp.ndarray]:
        """The chain engine's per-batch tables for (B, num_config)
        ``configs`` and a static sweep bound: built once a batch, since
        the selects do not change from cycle to cycle."""
        c = self.chain_consts()
        return fabric_chain.chain_tables(
            jax.vmap(self._selects)(configs), jnp.asarray(self.arrays.src),
            jnp.asarray(c["root"]), jnp.asarray(c["res"]),
            jnp.asarray(c["pin_src"]),
            jnp.asarray(self.fused_tables["read"]), max_depth)

    @jax.named_scope("canal.emulate")
    def _run_batch_local(self, configs: jnp.ndarray, ext: jnp.ndarray,
                         pe_cfgs: Dict[str, jnp.ndarray],
                         depths: jnp.ndarray, max_depth: int
                         ) -> jnp.ndarray:
        """One device's share of ``run_batch``: the chain tables once,
        then a scan of T cycles over a (local) batch of configurations."""
        b = configs.shape[0]
        state = self.init_state_batch(b)
        xs = jnp.swapaxes(ext, 0, 1)                    # (T, B, io)
        chains = self.chain_tables(configs, max_depth)

        def scan_fn(st, x):
            st, obs = self.step_batch(st, x, configs, pe_cfgs,
                                      depth=depths, max_depth=max_depth,
                                      chains=chains)
            return st, obs

        _, out = jax.lax.scan(scan_fn, state, xs)
        return jnp.swapaxes(out, 0, 1)                  # (B, T, io)

    def run_batch(self, configs: jnp.ndarray, ext_streams: jnp.ndarray,
                  pe_cfgs: Optional[Dict[str, jnp.ndarray]] = None,
                  depth: Optional[DepthSpec] = None,
                  shard: Optional[bool] = None) -> jnp.ndarray:
        """Evaluate B configurations in one ``lax.scan``.

        configs: (B, num_config); ext_streams: (B, T, num_io); pe_cfgs
        leaves (B, ...). Returns (B, T, num_io) observations — the batched
        equivalent of looping ``run`` over the B axis, bit-identical to it
        lane for lane. ``depth=None`` computes every configuration's own
        combinational depth on the host; a lane freezes once its own count
        is reached (masked early exit), so even an adversarial config with
        a combinational loop — whose values depend on the sweep count —
        sees exactly the sweeps its per-config ``run`` would.

        ``shard`` (default: auto, on when >1 device) splits the batch axis
        across ``jax.devices()`` via shard_map, padding B up to a multiple
        of the device count; on a single device the local path runs
        unsharded. Each cycle is one ``step_batch`` of the chain engine,
        whose tables are built once a batch, before the scan."""
        configs = jnp.asarray(configs)
        ext = jnp.asarray(ext_streams)
        b = configs.shape[0]
        if depth is None:
            host_cfgs = np.asarray(configs)
            depths_np = np.array(
                [self.combinational_depth(c) for c in host_cfgs],
                dtype=np.int32) if b else np.zeros(0, np.int32)
        else:
            depths_np = np.broadcast_to(
                np.asarray(depth, np.int32), (b,))
        max_depth = int(depths_np.max()) if b else 1
        if pe_cfgs is None:
            pe_cfgs = self.default_pe_cfg_batch(b)
        devices = jax.devices()
        n_dev = len(devices)
        use_shard = (n_dev > 1) if shard is None else shard
        if not use_shard or n_dev <= 1 or b == 0:
            return self._run_batch_jit(configs, ext, pe_cfgs,
                                       jnp.asarray(depths_np), max_depth)

        bp = -(-b // n_dev) * n_dev                     # ceil to devices
        pad = bp - b

        def pad_b(x):
            x = jnp.asarray(x)
            return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))

        out = self._sharded_program(tuple(devices))(
            pad_b(configs), pad_b(ext),
            {k: pad_b(v) for k, v in pe_cfgs.items()},
            jnp.asarray(np.pad(depths_np, (0, pad))), max_depth)
        return out[:b] if pad else out

    def _sharded_program(self, devices: Tuple):
        """``run_batch``'s program with the batch axis sharded across
        ``devices`` (shard_map), jitted once per device tuple."""
        program = self._sharded_programs.get(devices)
        if program is not None:
            return program
        spec = PartitionSpec("b")

        def sharded(c, e, p, d, max_depth):
            def local(c, e, p, d):
                return self._run_batch_local(c, e, p, d, max_depth)

            # check_vma=False: every operand/output is explicitly
            # batch-sharded
            return jax.shard_map(
                local, mesh=Mesh(np.array(devices), ("b",)),
                in_specs=(spec, spec, spec, spec), out_specs=spec,
                check_vma=False)(c, e, p, d)

        return self._sharded_programs.setdefault(
            devices, jax.jit(sharded, static_argnums=(4,)))

    # ------------------------------------------------- combinational depth
    def _selected_src_host(self, config: np.ndarray) -> np.ndarray:
        """Host-side selected source per node under ``config`` (N,)."""
        a = self.arrays
        sel = np.zeros(a.num_nodes, np.int64)
        mask = a.config_slot >= 0
        if a.num_config:
            cfg = np.asarray(config, np.int64)
            sel[mask] = cfg[a.config_slot[mask]]
        sel = np.clip(sel, 0, np.maximum(a.fanin_count - 1, 0))
        return a.src[np.arange(a.num_nodes), sel]

    def combinational_depth(self, config: np.ndarray,
                            margin: int = 1) -> int:
        """Sweeps needed to reach the fixpoint under ``config``: longest
        register-free chain of the *configured* network (each mux follows
        only its selected input), instead of the conservative fixed bound.

        Chains are rooted at pinned nodes (registers, externally driven IO,
        memory outputs, undriven nodes); a PE output sits one level above
        its deepest input. A legal configuration's active network is
        acyclic; combinational cycles through unconfigured default-0 muxes
        are detected and excluded (their values never stabilize and no
        routed path goes through them)."""
        a = self.arrays
        n = a.num_nodes
        src_sel = self._selected_src_host(config)
        pinned = (~a.is_driven) | a.is_reg
        if len(self.io_in_nodes):
            pinned[self.io_in_nodes] = True
        if len(self.mem_out):
            pinned[self.mem_out] = True
        derive = ~pinned
        depth = np.zeros(n + 1, np.int64)       # sentinel at n stays 0
        prev_changed: Optional[np.ndarray] = None
        cap = min(n + 2, 4096)
        for _ in range(cap):
            new = depth.copy()
            new[:n][derive] = depth[src_sel[derive]] + 1
            if self.num_pe:
                pe_depth = depth[self.pe_in].max(axis=1) + 1   # (n_pe,)
                for col in range(self.pe_out.shape[1]):
                    new[self.pe_out[:, col]] = pe_depth
            new[n] = 0
            changed = np.nonzero(new != depth)[0]
            depth = new
            if changed.size == 0:
                return int(depth.max()) + margin
            if (prev_changed is not None
                    and np.array_equal(changed, prev_changed)):
                # a set equal to its own successor set contains a cycle:
                # report the depth of the stable (acyclic) portion only
                stable = np.ones(n + 1, bool)
                stable[changed] = False
                d = int(depth[stable].max()) if stable.any() else 0
                return max(d + margin, 1)
            prev_changed = changed
        return cap

    def depth_for_route(self, edges: Sequence[Tuple[Node, Node]],
                        margin: int = 2) -> int:
        """Sweeps needed to emulate a routed application: longest
        register-free chain along the routed tree (PE core hops included),
        replacing the conservative ``len(edges) + 4`` bound."""
        sentinel = self.arrays.num_nodes
        is_reg = self.arrays.is_reg
        children: Dict[int, List[Tuple[int, int]]] = {}
        indeg: Dict[int, int] = {}
        nodes = set()

        def add_edge(u: int, v: int, w: int) -> None:
            children.setdefault(u, []).append((v, w))
            indeg[v] = indeg.get(v, 0) + 1
            nodes.add(u)
            nodes.add(v)

        for s, d in edges:
            add_edge(self.node_id[s], self.node_id[d], 1)
        # PE core hops are weight 0: _eval_pes runs after the gather, so a
        # PE output settles in the same sweep as its inputs
        for k in range(self.num_pe):
            ins = [int(i) for i in self.pe_in[k] if i != sentinel]
            for col in range(self.pe_out.shape[1]):
                out = int(self.pe_out[k, col])
                for i in ins:
                    add_edge(i, out, 0)
        # longest path over the routed DAG; registers restart the chain
        depth = {i: 0 for i in nodes}
        ready = [i for i in nodes if indeg.get(i, 0) == 0]
        seen = 0
        while ready:
            u = ready.pop()
            seen += 1
            du = 0 if is_reg[u] else depth[u]
            for v, w in children.get(u, ()):
                if not is_reg[v]:
                    depth[v] = max(depth[v], du + w)
                indeg[v] -= 1
                if indeg[v] == 0:
                    ready.append(v)
        if seen != len(nodes):
            # combinational loop through a PE (route feeds the PE its own
            # output): fall back to the conservative bound
            return len(list(edges)) + 4
        return max(depth.values(), default=0) + margin

    # ------------------------------------------------------- route → config
    def route_to_config(self, edges: Sequence[Tuple[Node, Node]]
                        ) -> np.ndarray:
        """Translate routed IR edges into a config vector: for every edge
        (src → dst) where dst is a mux, set dst's select to src's input
        index. Conflicting assignments raise (illegal route)."""
        config = np.zeros(self.num_config, dtype=np.int32)
        assigned: Dict[int, int] = {}
        for src, dst in edges:
            i = self.node_id[dst]
            slot = self.arrays.config_slot[i]
            if slot < 0:
                continue                    # single-input: hardwired
            sel = dst.fan_in.index(src)
            if i in assigned and assigned[i] != sel:
                raise ValueError(
                    f"conflicting mux assignment at {dst}: "
                    f"{assigned[i]} vs {sel}")
            assigned[i] = sel
            config[slot] = sel
        return config

    def structural_connectivity(self) -> Dict[Tuple, List[Tuple]]:
        """Connectivity as realized by the lowered tables — compared against
        the IR by repro.core.verify (paper: parse generated RTL)."""
        out: Dict[Tuple, List[Tuple]] = {}
        a = self.arrays
        for i, node in enumerate(self.nodes):
            keys = []
            for j in range(a.fanin_count[i]):
                keys.append(self.nodes[a.src[i, j]].node_key())
            out[node.node_key()] = keys
        return out


def compile_interconnect(ic: Interconnect) -> FabricModule:
    """The static-backend entry point (IR → hardware, §3.3)."""
    return FabricModule(ic)
