"""Device-resident batched annealing placement (§3.4, Eq. 2).

The host annealer in :mod:`detailed_place` proposes moves in a Python
loop and round-trips to the device once per temperature step to score a
candidate batch — placement is the last host-serial stage of a cold PnR
evaluation now that routing and emulation are device-accelerated. This
module replaces that loop with **one jitted device program**:

* K independent annealing chains run as a single ``lax.scan`` over
  temperature steps with the chain axis vmapped; per-chain move
  proposal uses ``jax.random`` (seed-deterministic across processes).
* Moves are encoded as (instance, target-slot) pairs over a dense
  *legal-tile table* partitioned by tile class (PE tiles vs memory
  columns, IO ring excluded), so mem-column / IO-ring legality holds by
  construction — an illegal placement is unrepresentable.
* Each chain scores a small candidate batch per step and applies the
  cheapest Metropolis-passing candidate (the documented
  best-passing-candidate semantics, vectorized: every candidate draws
  its own uniform, the accepted one is the min-cost passer).
* Eq. 2 cost deltas are incremental: only the nets touching the moved
  instances re-reduce their pin bounding boxes; the overlap term reads
  a per-chain occupancy integral image. The full per-net reduction —
  used to seed the chain state — is the ``repro.kernels.hpwl`` Pallas
  kernel on padded ``(n_nets, K, 2)`` pin tables.
* Chains sit on a geometric temperature ladder and periodically attempt
  replica exchange between neighbours (parallel tempering), so hot
  chains feed escapes to cold ones; the best placement seen by any
  chain wins.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.kernels import ops

from .packing import PackedGraph

#: candidate proposals per chain per temperature step
DEFAULT_CANDS = 4
#: temperature-ladder span: the hottest chain anneals this many times
#: hotter than the coldest (chain 0) at every step
DEFAULT_LADDER = 3.0
#: steps between replica-exchange attempts (even/odd neighbour pairs
#: alternate, so the whole ladder mixes)
DEFAULT_EXCHANGE_EVERY = 16


# ---------------------------------------------------------------------------
# Host-side table construction
# ---------------------------------------------------------------------------

def _net_members(packed: PackedGraph,
                 idx: Dict[str, int]) -> List[List[int]]:
    """Per-net placeable member instance indices (>=2 members only)."""
    out: List[List[int]] = []
    for net in packed.nets:
        members = [net.src[0]] + [s for s, _ in net.sinks]
        members = [idx[m] for m in members if m in idx]
        if len(members) >= 2:
            out.append(members)
    return out


def _legal_slot_tables(packed: PackedGraph,
                       placement: Dict[str, Tuple[int, int]],
                       movable: List[str],
                       width: int, height: int,
                       mem_columns: Sequence[int],
                       io_ring: bool):
    """The dense legal-tile tables that make moves legal by construction.

    Tiles are partitioned into classes — ``mem`` (memory columns, when
    any are declared) and ``pe`` (everything else) — minus the IO ring
    border (when enabled) and tiles pinned by immovable instances. Each
    movable instance draws move targets only from its own class range,
    mirroring :func:`global_place.legalize`'s ``legal_for`` rules."""
    mem_cols = set(int(c) for c in mem_columns)
    fixed_tiles = {placement[n] for n in placement if n not in set(movable)}
    tiles: Dict[str, List[Tuple[int, int]]] = {"pe": [], "mem": []}
    for x in range(width):
        for y in range(height):
            if io_ring and (x in (0, width - 1) or y in (0, height - 1)):
                continue
            if (x, y) in fixed_tiles:
                continue
            cls = "mem" if (mem_cols and x in mem_cols) else "pe"
            tiles[cls].append((x, y))

    slot_xy = np.array(tiles["pe"] + tiles["mem"], np.int32)
    ranges = {"pe": (0, len(tiles["pe"])),
              "mem": (len(tiles["pe"]), len(tiles["mem"]))}
    tile_slot = {tuple(t): s for s, t in enumerate(slot_xy.tolist())}

    inst_lo = np.zeros(len(movable), np.int32)
    inst_size = np.zeros(len(movable), np.int32)
    slot0 = np.zeros(len(movable), np.int32)
    for i, name in enumerate(movable):
        kind = packed.placeable[name].kind
        cls = "mem" if (kind == "mem" and mem_cols) else "pe"
        lo, size = ranges[cls]
        if size == 0:
            raise ValueError(f"no legal tiles for {name} (class {cls})")
        inst_lo[i], inst_size[i] = lo, size
        tile = tuple(placement[name])
        if tile not in tile_slot or not lo <= tile_slot[tile] < lo + size:
            raise ValueError(
                f"instance {name} at {tile} is outside its legal tile "
                f"class {cls!r} — batched placement needs a legal seed")
        slot0[i] = tile_slot[tile]
    return slot_xy, inst_lo, inst_size, slot0


def _eq2_terms(bboxes: jnp.ndarray, occ: jnp.ndarray,
               gamma, alpha) -> jnp.ndarray:
    """Per-net Eq. 2 terms from (n, 4) boxes + an occupancy grid."""
    ii = jnp.pad(jnp.cumsum(jnp.cumsum(occ, axis=0), axis=1),
                 ((1, 0), (1, 0)))
    x0, x1 = bboxes[:, 0], bboxes[:, 1]
    y0, y1 = bboxes[:, 2], bboxes[:, 3]
    overlap = (ii[x1 + 1, y1 + 1] - ii[x0, y1 + 1]
               - ii[x1 + 1, y0] + ii[x0, y0]).astype(jnp.float32)
    hpwl = ((x1 - x0) + (y1 - y0)).astype(jnp.float32)
    return jnp.maximum(hpwl - gamma * overlap, 1.0) ** alpha


def eq2_cost(packed: PackedGraph, placement: Dict[str, Tuple[int, int]],
             width: int, height: int,
             gamma: float = 0.3, alpha: float = 2.0) -> float:
    """The exact Eq. 2 cost of a placement (per-net boxes via the
    ``repro.kernels.hpwl`` Pallas kernel) — the common yardstick the
    host oracle and the batched chains are compared on."""
    inst_order = list(packed.placeable)
    idx = {n: i for i, n in enumerate(inst_order)}
    members = _net_members(packed, idx)
    if not members:
        return 0.0
    kp = max(len(m) for m in members)
    pins = np.zeros((len(members), kp, 2), np.int32)
    mask = np.zeros((len(members), kp), np.int32)
    for n, mem in enumerate(members):
        for j, gi in enumerate(mem):
            pins[n, j] = placement[inst_order[gi]]
            mask[n, j] = 1
    bboxes = ops.net_bboxes(jnp.asarray(pins), jnp.asarray(mask))
    occ = np.zeros((width, height), np.float32)
    for (x, y) in placement.values():
        occ[x, y] = 1.0
    terms = _eq2_terms(bboxes, jnp.asarray(occ),
                       jnp.float32(gamma), jnp.float32(alpha))
    return float(jnp.sum(terms))


# ---------------------------------------------------------------------------
# The device program
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("n_steps", "n_chains", "cands", "exchange_every"))
@jax.named_scope("canal.anneal")
def _anneal(slot_xy, mov_gid, inst_lo, inst_size, net_pins, net_mask,
            mov_nets, pos0, occ0, slot0, owner0, bbox0,
            seed, gamma, alpha, t0, t_min, ladder,
            n_steps: int, n_chains: int, cands: int, exchange_every: int):
    """K parallel-tempering annealing chains as one scan-over-steps.

    All tables are device arrays; ``bbox0`` is ``(n_nets + 1, 4)`` (the
    trailing row is the scatter sink for padded affected-net slots).
    Returns ``(best_slot, best_cost)`` stacked over chains."""
    n_mov = slot0.shape[0]
    n_nets = bbox0.shape[0] - 1
    chain_ids = jnp.arange(n_chains)
    base_key = jax.random.PRNGKey(seed)
    decay = (t_min / t0) ** (1.0 / max(n_steps, 1))
    #: chain k anneals ladder**(k/(K-1)) hotter than chain 0
    ladder_f = ladder ** (chain_ids.astype(jnp.float32)
                          / max(n_chains - 1, 1))

    def terms_total(bbox, occ):
        return jnp.sum(_eq2_terms(bbox[:n_nets], occ, gamma, alpha))

    cost0 = terms_total(bbox0, occ0)

    def chain_step(slot, owner, pos, occ, bbox, cost, key, temp):
        kc, ks, ku = jax.random.split(key, 3)
        mi = jax.random.randint(kc, (cands,), 0, n_mov)
        draw = jax.random.randint(ks, (cands,), 0, jnp.int32(1 << 30))
        tgt = inst_lo[mi] + draw % inst_size[mi]
        u = jax.random.uniform(ku, (cands,))

        def eval_cand(i, t_slot):
            src = slot[i]
            j = owner[t_slot]                    # another movable, or -1
            valid = t_slot != src
            swap = j >= 0
            jc = jnp.maximum(j, 0)
            gi = mov_gid[i]
            gj = jnp.where(swap, mov_gid[jc], gi)
            xy_i = slot_xy[t_slot]
            xy_j = jnp.where(swap, slot_xy[src], xy_i)
            # occupancy moves only on a relocate (swap leaves it fixed)
            docc = jnp.where(swap, 0.0, 1.0)
            sxy = slot_xy[src]
            occ2 = occ.at[sxy[0], sxy[1]].add(-docc)
            occ2 = occ2.at[xy_i[0], xy_i[1]].add(docc)
            # incremental re-reduce: only nets touching the movers
            aff = jnp.concatenate(
                [mov_nets[i], jnp.where(swap, mov_nets[jc], -1)])
            live = aff >= 0
            affc = jnp.maximum(aff, 0)
            pidx = net_pins[affc]                # (2M, Kp)
            pxy = pos[pidx]                      # (2M, Kp, 2)
            pxy = jnp.where((pidx == gi)[..., None], xy_i[None, None],
                            pxy)
            pxy = jnp.where((swap & (pidx == gj))[..., None],
                            xy_j[None, None], pxy)
            m = net_mask[affc] > 0
            big = jnp.int32(1 << 20)
            px, py = pxy[..., 0], pxy[..., 1]
            nb = jnp.stack([
                jnp.min(jnp.where(m, px, big), axis=1),
                jnp.max(jnp.where(m, px, -big), axis=1),
                jnp.min(jnp.where(m, py, big), axis=1),
                jnp.max(jnp.where(m, py, -big), axis=1),
            ], axis=1)
            # padded slots scatter into the sink row n_nets; duplicate
            # net ids scatter identical boxes, so order is irrelevant
            row = jnp.where(live, affc, n_nets)
            bbox2 = bbox.at[row].set(nb)
            cost2 = terms_total(bbox2, occ2)
            # applied state (selected lazily by the accept step below)
            slot2 = slot.at[i].set(t_slot)
            slot2 = slot2.at[jnp.where(swap, jc, i)].set(
                jnp.where(swap, src, t_slot))
            owner2 = owner.at[src].set(jnp.where(swap, jc, -1))
            owner2 = owner2.at[t_slot].set(i)
            pos2 = pos.at[gi].set(xy_i)
            pos2 = pos2.at[jnp.where(swap, gj, gi)].set(
                jnp.where(swap, xy_j, xy_i))
            return cost2, valid, slot2, owner2, pos2, occ2, bbox2

        c2, valid, slot2, owner2, pos2, occ2, bbox2 = \
            jax.vmap(eval_cand)(mi, tgt)
        d = c2 - cost
        passed = valid & ((d <= 0)
                          | (u < jnp.exp(-d / jnp.maximum(temp, 1e-6))))
        # best-passing-candidate: cheapest candidate whose own
        # Metropolis draw passed (== walking candidates cheapest-first
        # and accepting the first passer)
        score = jnp.where(passed, c2, jnp.inf)
        b = jnp.argmin(score)
        take = score[b] < jnp.inf

        def pick(new, old):
            return jnp.where(take, new[b], old)

        return (pick(slot2, slot), pick(owner2, owner), pick(pos2, pos),
                pick(occ2, occ), pick(bbox2, bbox), pick(c2, cost))

    def exchange(t, costs, temps, key):
        """Neighbour replica-exchange permutation for this step (identity
        off-cadence). Standard PT acceptance between ladder neighbours:
        p = min(1, exp((E_a - E_b)(1/T_a - 1/T_b)))."""
        k_ids = jnp.arange(n_chains)
        ex_round = (t % exchange_every) == (exchange_every - 1)
        off = (t // exchange_every) % 2
        left = ((k_ids - off) % 2 == 0) & (k_ids + 1 < n_chains)
        partner_of_left = jnp.minimum(k_ids + 1, n_chains - 1)
        logp = ((costs - costs[partner_of_left])
                * (1.0 / temps - 1.0 / temps[partner_of_left]))
        u = jax.random.uniform(key, (n_chains,))
        acc_left = left & (jnp.log(jnp.maximum(u, 1e-30)) < logp)
        right = jnp.roll(acc_left, 1) & (k_ids > 0)
        perm = jnp.where(acc_left, k_ids + 1,
                         jnp.where(right, k_ids - 1, k_ids))
        return jnp.where(ex_round, perm, k_ids)

    def body(carry, t):
        slot, owner, pos, occ, bbox, cost, best_cost, best_slot = carry
        temps = (t0 * decay ** t) * ladder_f
        step_key = jax.random.fold_in(base_key, t)
        keys = jax.vmap(lambda c: jax.random.fold_in(step_key, c))(
            chain_ids)
        slot, owner, pos, occ, bbox, cost = jax.vmap(chain_step)(
            slot, owner, pos, occ, bbox, cost, keys, temps)
        better = cost < best_cost
        best_cost = jnp.where(better, cost, best_cost)
        best_slot = jnp.where(better[:, None], slot, best_slot)
        perm = exchange(t, cost, temps,
                        jax.random.fold_in(step_key, n_chains))
        carry = tuple(x[perm] for x in
                      (slot, owner, pos, occ, bbox, cost,
                       best_cost, best_slot))
        return carry, None

    def tile(x):
        return jnp.broadcast_to(x, (n_chains,) + x.shape)

    carry0 = (tile(slot0), tile(owner0), tile(pos0), tile(occ0),
              tile(bbox0), jnp.full((n_chains,), cost0),
              jnp.full((n_chains,), cost0), tile(slot0))
    carry, _ = jax.lax.scan(body, carry0, jnp.arange(n_steps))
    _, _, _, _, _, _, best_cost, best_slot = carry
    return best_slot, best_cost


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

def batched_place(packed: PackedGraph,
                  placement: Dict[str, Tuple[int, int]],
                  width: int, height: int,
                  mem_columns: Sequence[int] = (),
                  io_ring: bool = True,
                  gamma: float = 0.3, alpha: float = 2.0,
                  n_steps: int = 300, n_chains: int = 16,
                  cands: int = DEFAULT_CANDS,
                  t0: float = 2.0, t_min: float = 0.01,
                  seed: int = 0,
                  exchange_every: int = DEFAULT_EXCHANGE_EVERY,
                  ladder: float = DEFAULT_LADDER,
                  return_cost: bool = False):
    """Anneal the legalized placement on-device: K parallel-tempering
    chains, one jitted scan, best chain wins. Same contract as
    :func:`detailed_place.detailed_place` (only pe/mem instances move;
    legality is structural). Deterministic for a fixed ``seed``."""
    inst_order = list(packed.placeable)
    idx = {n: i for i, n in enumerate(inst_order)}
    members = _net_members(packed, idx)
    movable = [n for n in inst_order
               if packed.placeable[n].kind in ("pe", "mem")]
    if not members or not movable:
        return (dict(placement), 0.0) if return_cost else dict(placement)

    n_nets = len(members)
    kp = max(len(m) for m in members)
    net_pins = np.zeros((n_nets, kp), np.int32)
    net_mask = np.zeros((n_nets, kp), np.int32)
    for n, mem in enumerate(members):
        net_pins[n, :len(mem)] = mem
        net_mask[n, :len(mem)] = 1

    mov_gid = np.array([idx[n] for n in movable], np.int32)
    touch: Dict[int, List[int]] = {i: [] for i in range(len(movable))}
    mov_of_gid = {int(g): i for i, g in enumerate(mov_gid)}
    for n, mem in enumerate(members):
        for gi in set(mem):
            if gi in mov_of_gid:
                touch[mov_of_gid[gi]].append(n)
    m_max = max(1, max(len(v) for v in touch.values()))
    mov_nets = np.full((len(movable), m_max), -1, np.int32)
    for i, nets_i in touch.items():
        mov_nets[i, :len(nets_i)] = nets_i

    slot_xy, inst_lo, inst_size, slot0 = _legal_slot_tables(
        packed, placement, movable, width, height, mem_columns, io_ring)
    owner0 = np.full(len(slot_xy), -1, np.int32)
    owner0[slot0] = np.arange(len(movable), dtype=np.int32)

    pos0 = np.array([placement[n] for n in inst_order], np.int32)
    occ0 = np.zeros((width, height), np.float32)
    for (x, y) in placement.values():
        occ0[x, y] = 1.0

    # seed the chain state with the full per-net reduction — the Pallas
    # HPWL/bbox kernel on the padded (n_nets, K, 2) pin table
    pins0 = pos0[net_pins]
    bbox0 = ops.net_bboxes(jnp.asarray(pins0), jnp.asarray(net_mask))
    with trace.span("device.wait"):
        bbox0 = np.asarray(bbox0)
    bbox0 = np.concatenate([bbox0, np.zeros((1, 4), np.int32)])

    best_slot, best_cost = _anneal(
        jnp.asarray(slot_xy), jnp.asarray(mov_gid), jnp.asarray(inst_lo),
        jnp.asarray(inst_size), jnp.asarray(net_pins),
        jnp.asarray(net_mask), jnp.asarray(mov_nets), jnp.asarray(pos0),
        jnp.asarray(occ0), jnp.asarray(slot0), jnp.asarray(owner0),
        jnp.asarray(bbox0),
        jnp.int32(seed), jnp.float32(gamma), jnp.float32(alpha),
        jnp.float32(t0), jnp.float32(t_min), jnp.float32(ladder),
        n_steps=int(n_steps), n_chains=int(n_chains), cands=int(cands),
        exchange_every=int(exchange_every))
    with trace.span("device.wait"):
        best_slot = np.asarray(best_slot)
        best_cost = np.asarray(best_cost)
    win = int(np.argmin(best_cost))

    out = {n: (int(x), int(y)) for n, (x, y) in placement.items()}
    for i, name in enumerate(movable):
        x, y = slot_xy[best_slot[win, i]]
        out[name] = (int(x), int(y))
    if return_cost:
        return out, float(best_cost[win])
    return out
