"""Chain-collapsed fixpoint: the engine of the fabric's batched emulation
(``FabricModule.step_batch``), and the PE ALU datapath it evaluates.

Within one fabric cycle the mux selects are fixed, so every sweep of the
fixpoint copies into each driven node the value its selected input held
one sweep before. A node ``j`` whose chain of selected inputs reaches a
*root* ``r`` in ``h`` hops therefore holds, after ``t`` sweeps, the value
``r`` held after ``t - h`` sweeps, or its zero background while ``h >
t``. Roots are the nodes a sweep does not copy into: held (undriven) and
pinned nodes, whose value is a constant of the cycle, the zero sentinel,
and the PE outputs, which the sweeps rewrite. Only the 2P PE results
change from sweep to sweep, so the fixpoint reduces to a series ``S[s]``
of them (2P words a lane), driven by the 4P PE input slots alone; every
other node is read off that series once, after the loop.

``chain_tables`` finds each node's root and hop count by pointer doubling
over the selected inputs, once per batch (the selects depend only on the
configurations). ``chain_fixpoint`` runs one cycle from the tables.

Contract: ``ref.fabric_fused_batch_ref`` (the sweep oracle, kept as the
test reference) started from the pinned background, ``vals0 ==
pin_vals``, zero off the pins, with every PE output node held and not
pinned; ``FabricModule.chain_consts`` checks the last. Bit-identical on
every node for every configuration, cyclic ones included: a chain that
reaches no root within the sweep budget reads 0, as the oracle's zero
background does, and feedback through a PE is carried by the series.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

# PE ALU candidate order; must match repro.core.tiles.PECore.OPS
# (repro.core.lowering asserts the correspondence at import time).
PE_OPS = ("add", "sub", "mul", "and", "or", "xor", "shl", "shr", "min",
          "max", "abs", "sel", "const", "pass")


def pe_alu_candidates(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
                      const: jnp.ndarray) -> jnp.ndarray:
    """All PE ALU results, stacked (n_ops, ...) in ``PE_OPS`` order.

    The device copy of the PE datapath: the chain engine, its sweep
    oracle (``ref.fabric_fused_batch_ref``) and the per-config
    ``FabricModule._eval_pes`` select rows out of this stack with the
    configured opcode. Shifts take the amount's low four bits, as
    ``PECore.evaluate`` does."""
    shift_b = b & 15
    return jnp.stack([
        a + b, a - b, a * b, a & b, a | b, a ^ b,
        a << shift_b, a >> shift_b, jnp.minimum(a, b),
        jnp.maximum(a, b), jnp.abs(a - b),
        jnp.where((a & 1) == 1, b, c), const, a,
    ], axis=0)


def chain_tables(sel: jnp.ndarray, src: jnp.ndarray, root: jnp.ndarray,
                 res: jnp.ndarray, pin_src: jnp.ndarray, read: jnp.ndarray,
                 max_depth: int) -> Dict[str, jnp.ndarray]:
    """Per lane, for each node of ``read``: ``hop``, the sweeps its value
    trails its chain's root (capped at ``max_depth + 1``, which reads 0
    at every depth); ``res``, the root's index into the flattened (res0,
    res1) PE results (``2P`` where the root is no PE output); ``cidx``,
    the root's index into the cycle's pinned state.

    sel (B, N) mux selects; src (N, F) fan-in table padded with the
    sentinel N; root, res and pin_src (N + 1,) node tables, the sentinel
    included (a root, no PE output, pinned to the state's zero); read
    (K,) node ids."""
    b, n = sel.shape
    node = jnp.arange(n + 1, dtype=jnp.int32)
    parent = src[node[None, :n], sel]
    parent = jnp.concatenate(
        [parent, jnp.full((b, 1), n, jnp.int32)], axis=1)
    is_root = root[None, :] > 0
    parent = jnp.where(is_root, node[None, :], parent)
    hop = jnp.where(is_root, 0, 1).astype(jnp.int32)
    # after k rounds: parent is 2**k hops up (held at the root), hop the
    # hops taken; 2**k > max_depth covers every chain a sweep can see
    for _ in range(max(int(max_depth), 1).bit_length()):
        hop = hop + jnp.take_along_axis(hop, parent, axis=1)
        parent = jnp.take_along_axis(parent, parent, axis=1)
    cap = int(max_depth) + 1
    hop = jnp.where(root[parent] > 0, jnp.minimum(hop, cap), cap)
    at = parent[:, read]
    return {"hop": hop[:, read], "res": res[at], "cidx": pin_src[at]}


def chain_fixpoint(chains: Dict[str, jnp.ndarray], pin_state: jnp.ndarray,
                   depths: jnp.ndarray, op: jnp.ndarray, const: jnp.ndarray,
                   imm_mask: jnp.ndarray, imm_val: jnp.ndarray,
                   max_depth: int, word: int = 0xFFFF) -> jnp.ndarray:
    """One cycle's fixpoint for B lanes, each frozen after its own
    ``depths`` sweeps: the (B, K) values of the nodes ``chains`` was
    built for. The first 4P of them must be the PE inputs, (P, 4) row
    major.

    pin_state (B, S): the cycle's pinned values, zero at ``S - 1``; op,
    const (B, P) and imm_mask, imm_val (B, P, 4): the PE programs, as
    ``ref.fabric_fused_batch_ref`` takes them."""
    b, p = op.shape
    n_res, n_slot = 2 * p, 4 * p
    hop, res = chains["hop"], chains["res"]
    const_v = jnp.take_along_axis(pin_state, chains["cidx"], axis=1)
    is_res = res < n_res
    length = (int(max_depth) + 1) * n_res
    # history of the PE results, sweep-major: S[s] at [s * 2P, (s+1) * 2P);
    # S[0] is the background, 0 on an unpinned PE output
    series = jnp.zeros((b, length), jnp.int32)
    # a slot that is itself a PE output reads the result held from the
    # last sweep, not the one being computed
    s_hop = hop[:, :n_slot]
    s_res, s_is_res = res[:, :n_slot], is_res[:, :n_slot]
    s_hop = jnp.where(s_is_res & (s_hop == 0), 1, s_hop)
    s_at = s_res - s_hop * n_res
    s_const = const_v[:, :n_slot]
    imm = imm_mask.reshape(b, n_slot) > 0
    imm_val = imm_val.reshape(b, n_slot)
    # the opcode as a mask over the candidates: the selection fuses with
    # the ALU into one loop, where a gather would materialize the stack
    opcodes = jnp.arange(len(PE_OPS), dtype=jnp.int32)
    pick = op[None] == opcodes[:, None, None]

    def sweep(s, series):
        at = jnp.clip(s_at + s * n_res, 0, length - 1)
        ins = jnp.where(s_is_res, jnp.take_along_axis(series, at, axis=1),
                        s_const)
        ins = jnp.where(s_hop > s, 0, ins)
        ins = jnp.where(imm, imm_val, ins).reshape(b, p, 4)
        a, bb, c = ins[..., 0], ins[..., 1], ins[..., 2]
        cand = pe_alu_candidates(a, bb, c, const)
        res0 = jnp.sum(jnp.where(pick, cand, 0), axis=0) & word
        out = jnp.stack([res0, a & word], axis=-1).reshape(b, n_res)
        return jax.lax.dynamic_update_slice(series, out, (0, s * n_res))

    series = jax.lax.fori_loop(1, int(max_depth) + 1, sweep, series)
    d = depths[:, None]
    at = jnp.clip((d - hop) * n_res + res, 0, length - 1)
    got = jnp.where(is_res, jnp.take_along_axis(series, at, axis=1),
                    const_v)
    return jnp.where(hop > d, 0, got)
