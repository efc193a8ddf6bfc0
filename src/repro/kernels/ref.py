"""Pure-jnp oracles for the kernels (the correctness contracts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .fabric_chain import pe_alu_candidates


def fabric_fused_batch_ref(vals0: jnp.ndarray, sel: jnp.ndarray,
                           pin_vals: jnp.ndarray, depths: jnp.ndarray,
                           op: jnp.ndarray, const: jnp.ndarray,
                           imm_mask: jnp.ndarray, imm_val: jnp.ndarray,
                           src: jnp.ndarray, keep: jnp.ndarray,
                           pin_mask: jnp.ndarray, pe_in: jnp.ndarray,
                           pe_out: jnp.ndarray, max_depth: int,
                           word: int = 0xFFFF) -> jnp.ndarray:
    """The N-wide sweep oracle of the chain engine
    (``fabric_chain.chain_fixpoint``): a vmapped lane loop of gather ->
    hold-undriven -> re-pin -> PE-eval sweeps over every node, each lane
    frozen once its own ``depths`` count is reached. PE outputs are named
    by ``pe_out`` (n_pe, n_cols) node ids. Returns the (B, N) values."""
    n_pe = pe_out.shape[0]

    def lane(v0, s, pv, d, o, cst, im, iv):
        def sweep(t, v):
            v_ext = jnp.concatenate([v, jnp.zeros(1, jnp.int32)])
            picked = jnp.take_along_axis(src, s[:, None], axis=1)[:, 0]
            nv = v_ext[picked]
            nv = jnp.where(keep > 0, v, nv)
            nv = jnp.where(pin_mask > 0, pv, nv)
            nv_ext = jnp.concatenate([nv, jnp.zeros(1, jnp.int32)])
            ins = nv_ext[pe_in]
            ins = jnp.where(im > 0, iv, ins)
            a, b, c = ins[:, 0], ins[:, 1], ins[:, 2]
            cand = pe_alu_candidates(a, b, c, cst)
            res0 = jnp.take_along_axis(cand, o[None, :], axis=0)[0] & word
            res1 = a & word
            if n_pe:
                nv = nv.at[pe_out[:, 0]].set(res0[:n_pe])
                if pe_out.shape[1] > 1:
                    nv = nv.at[pe_out[:, 1]].set(res1[:n_pe])
            return jnp.where(t < d, nv, v)

        return jax.lax.fori_loop(0, max_depth, sweep, v0)

    return jax.vmap(lane)(vals0, sel, pin_vals, depths, op, const,
                          imm_mask, imm_val)


def hpwl_ref(pins: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    big = jnp.int32(1 << 20)
    m = mask > 0
    x, y = pins[:, :, 0], pins[:, :, 1]
    xmax = jnp.max(jnp.where(m, x, -big), axis=1)
    xmin = jnp.min(jnp.where(m, x, big), axis=1)
    ymax = jnp.max(jnp.where(m, y, -big), axis=1)
    ymin = jnp.min(jnp.where(m, y, big), axis=1)
    return jnp.where(m.any(axis=1), (xmax - xmin) + (ymax - ymin), 0)


def net_bboxes_ref(pins: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Per-net (xmin, xmax, ymin, ymax) boxes; empty nets -> zero box."""
    big = jnp.int32(1 << 20)
    m = mask > 0
    x, y = pins[:, :, 0], pins[:, :, 1]
    box = jnp.stack([
        jnp.min(jnp.where(m, x, big), axis=1),
        jnp.max(jnp.where(m, x, -big), axis=1),
        jnp.min(jnp.where(m, y, big), axis=1),
        jnp.max(jnp.where(m, y, -big), axis=1),
    ], axis=1)
    return jnp.where(m.any(axis=1)[:, None], box, 0)


def minplus_ref(d: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """min(d, min_i(d_i + w_ij)) batched over rows of d."""
    return jnp.minimum(d, jnp.min(d[:, :, None] + w[None], axis=1))


def minplus_fixpoint_ref(d0: jnp.ndarray, w: jnp.ndarray,
                         iters: int) -> jnp.ndarray:
    """``iters`` tropical relaxations — the contract of the blocked
    kernel's fixpoint loop (and of ``minplus_wavefront`` once ``iters``
    reaches the Bellman-Ford bound)."""
    d = d0
    for _ in range(iters):
        d = minplus_ref(d, w)
    return d


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True) -> jnp.ndarray:
    """Naive softmax attention. q: (BH, Sq, D), k/v: (BH, Skv, D)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(skv)[None, :]
        s = jnp.where(qi >= ki, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
            b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Naive SSD recurrence (the semantics the chunked kernel must match).

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T ;  y_t = h_t C_t
    x: (BH, L, P), dt: (BH, L), a: (BH,), b/c: (BH, L, N) -> y (BH, L, P)
    """

    def one(xh, dth, ah, bh_, ch):
        def step(h, inp):
            xt, dtt, bt, ct = inp
            h = jnp.exp(dtt * ah) * h + dtt * jnp.outer(xt, bt)
            return h, h @ ct

        p, n = xh.shape[-1], bh_.shape[-1]
        h0 = jnp.zeros((p, n), jnp.float32)
        _, y = jax.lax.scan(step, h0,
                            (xh.astype(jnp.float32),
                             dth.astype(jnp.float32),
                             bh_.astype(jnp.float32),
                             ch.astype(jnp.float32)))
        return y

    return jax.vmap(one)(x, dt, a, b, c).astype(x.dtype)
