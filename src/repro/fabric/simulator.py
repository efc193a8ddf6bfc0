"""Application emulation on a generated fabric.

Given a placed-and-routed application (see ``repro.core.pnr``), drive the
static fabric cycle by cycle: external streams enter at IO tiles, PEs
compute, and the emulator collects outputs. Used by the integration tests
to check that *applications* (not just connections) behave correctly on
the generated interconnect.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core import trace
from repro.core.graph import Node
from repro.core.lowering import FabricModule, PE_OP_IDS


class AppEmulator:
    """Binds a routed application to a fabric and runs it."""

    def __init__(self, fabric: FabricModule,
                 route_edges: Sequence[Tuple[Node, Node]],
                 pe_ops: Dict[Tuple[int, int], Tuple[str, int]],
                 pe_imms: Optional[Dict[Tuple[int, int],
                                        Dict[int, int]]] = None,
                 depth: Optional[int] = None,
                 pe_delays: Optional[Dict[Tuple[int, int],
                                          Sequence[int]]] = None):
        self.fabric = fabric
        self.config = jnp.asarray(fabric.route_to_config(route_edges))
        n = max(fabric.num_pe, 1)
        ops = np.full(n, PE_OP_IDS["pass"], np.int32)
        consts = np.zeros(n, np.int32)
        imm_mask = np.zeros((n, 4), np.int32)
        imm_val = np.zeros((n, 4), np.int32)
        reg_mask = np.zeros((n, 4), np.int32)
        coord_to_pe = {c: i for i, c in enumerate(fabric.pe_coords)}
        for coord, (op, const) in pe_ops.items():
            ops[coord_to_pe[coord]] = PE_OP_IDS[op]
            consts[coord_to_pe[coord]] = const
        for coord, ports in (pe_imms or {}).items():
            for port_idx, val in ports.items():
                imm_mask[coord_to_pe[coord], port_idx] = 1
                imm_val[coord_to_pe[coord], port_idx] = val
        for coord, ports in (pe_delays or {}).items():
            for port_idx in ports:
                reg_mask[coord_to_pe[coord], port_idx] = 1
        self.pe_cfg = {"op": jnp.asarray(ops), "const": jnp.asarray(consts),
                       "imm_mask": jnp.asarray(imm_mask),
                       "imm_val": jnp.asarray(imm_val),
                       "reg_mask": jnp.asarray(reg_mask)}
        self.io_index = {c: i for i, c in enumerate(fabric.io_coords)}
        # fixpoint sweeps: longest register-free chain of the routed tree
        # (replaces the conservative len(route_edges) + 4 bound)
        self.depth = (depth if depth is not None
                      else fabric.depth_for_route(route_edges))

    @classmethod
    def from_pnr(cls, fabric: FabricModule, packed, result,
                 depth: Optional[int] = None) -> "AppEmulator":
        """Bind a PnRResult directly (packing-aware): folded constants
        become PE immediates, and app registers (absorbed into a PE input
        or placed as a ``pass`` PE) delay their PE input one cycle."""
        pe_ops: Dict[Tuple[int, int], Tuple[str, int]] = {}
        pe_imms: Dict[Tuple[int, int], Dict[int, int]] = {}
        pe_delays: Dict[Tuple[int, int], List[int]] = {}
        delayed = packed.delayed_ports()
        for name, inst in packed.placeable.items():
            if inst.kind != "pe":
                continue
            xy = result.placement[name]
            pe_ops[xy] = (inst.op, inst.const)
            for port, val in packed.const_ports.get(name, {}).items():
                pe_imms.setdefault(xy, {})[int(port[-1])] = val
            pe_delays[xy] = [int(port[-1]) for port in delayed.get(name, ())]
        return cls(fabric, result.route_edges(), pe_ops, pe_imms,
                   depth=depth, pe_delays=pe_delays)

    def ext_stream(self, inputs: Dict[Tuple[int, int], np.ndarray],
                   cycles: int) -> np.ndarray:
        """Dense (cycles, num_io) drive matrix; streams longer than the
        emulation window are truncated."""
        ext = np.zeros((cycles, self.fabric.num_io), np.int32)
        for coord, stream in inputs.items():
            stream = np.asarray(stream)[:cycles]
            ext[:len(stream), self.io_index[coord]] = stream
        return ext

    def run(self, inputs: Dict[Tuple[int, int], np.ndarray], cycles: int
            ) -> Dict[Tuple[int, int], np.ndarray]:
        ext = self.ext_stream(inputs, cycles)
        obs = self.fabric.run(self.config, jnp.asarray(ext),
                              pe_cfg=self.pe_cfg, depth=self.depth)
        obs = np.asarray(obs)
        return {c: obs[:, i] for c, i in self.io_index.items()}


def run_apps_batch(emulators: Sequence[AppEmulator],
                   inputs_list: Sequence[Dict[Tuple[int, int], np.ndarray]],
                   cycles: int,
                   shard: Optional[bool] = None,
                   io_chunk: Optional[int] = None
                   ) -> List[Dict[Tuple[int, int], np.ndarray]]:
    """Emulate several routed applications on the *same* fabric as one
    batch: all configs/PE programs/IO streams advance together through a
    single ``FabricModule.run_batch`` scan. The engine is the chain
    engine (``kernels/fabric_chain.py``), whose sweeps evaluate only the
    4P PE input slots; the fused batched Pallas kernel when the fabric
    was compiled with ``use_pallas=True``. Both are tested against the
    N-wide sweep oracle, ``kernels/ref.py`` ``fabric_fused_batch_ref``.

    Each app sweeps exactly its own routed combinational depth — lanes
    with shallower routes freeze early instead of padding to the batch
    max — so this is bit-identical to ``[e.run(i, cycles) for e, i in
    zip(...)]`` while compiling one program for the whole batch — the DSE
    bulk-evaluation path. ``shard`` forwards to ``run_batch``: the app
    axis is split across devices when more than one is visible.
    ``io_chunk`` forwards too: on the Pallas fused engine, long stimulus
    traces stream from HBM in chunks of that many cycles instead of
    materializing (B, T, io) next to the value matrices.

    The ``emulate.run`` span records ``sweeps`` (the static sweep bound
    times the cycles) and ``slots_per_sweep``, the node values the
    engine gathers per lane per sweep."""
    if not emulators:
        return []
    fab = emulators[0].fabric
    if any(e.fabric is not fab for e in emulators):
        raise ValueError("batched emulation requires a shared fabric")
    depths = np.array([e.depth for e in emulators], dtype=np.int32)
    lanes = len(emulators)
    with trace.span("emulate.run", lanes=lanes, cycles=cycles,
                    app_cycles=lanes * cycles,
                    sweeps=int(depths.max()) * cycles,
                    slots_per_sweep=fab.slots_per_sweep):
        ext = np.stack([e.ext_stream(i, cycles)
                        for e, i in zip(emulators, inputs_list)])
        configs = jnp.stack([e.config for e in emulators])
        pe_cfgs = {k: jnp.stack([e.pe_cfg[k] for e in emulators])
                   for k in emulators[0].pe_cfg}
        out = fab.run_batch(configs, jnp.asarray(ext), pe_cfgs=pe_cfgs,
                            depth=depths, shard=shard, io_chunk=io_chunk)
        with trace.span("device.wait"):
            obs = np.asarray(out)
        return [{c: obs[b, :, i] for c, i in e.io_index.items()}
                for b, e in enumerate(emulators)]
