"""Kernel micro-benchmarks: interpret-mode correctness + host us/call.

On CPU the Pallas kernels run interpreted (correctness only — TPU is the
perf target); ``derived`` reports the max abs error vs the jnp oracle.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.kernels import ops, ref

from .common import emit, timed


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    lines = []

    pins = jnp.asarray(rng.integers(0, 64, (1024, 8, 2)).astype(np.int32))
    mask = jnp.asarray((rng.random((1024, 8)) < 0.8).astype(np.int32))
    out, us = timed(lambda: ops.hpwl(pins, mask).block_until_ready())
    err = int(np.abs(np.asarray(out)
                     - np.asarray(ref.hpwl_ref(pins, mask))).max())
    lines.append(emit("kernel/hpwl", us, f"maxerr={err}"))

    d = jnp.asarray((rng.random((4, 256)) * 9).astype(np.float32))
    w = np.where(rng.random((256, 256)) < 0.05,
                 rng.random((256, 256)) * 3, 1e30)
    np.fill_diagonal(w, 0.0)
    w = jnp.asarray(w.astype(np.float32))
    out, us = timed(lambda: ops.minplus_step(d, w).block_until_ready())
    err = float(np.abs(np.asarray(out)
                       - np.asarray(ref.minplus_ref(d, w))).max())
    lines.append(emit("kernel/minplus", us, f"maxerr={err:.2e}"))

    sq = 256 if quick else 512
    q = jnp.asarray(rng.standard_normal((1, 4, sq, 64)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, sq, 64)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, sq, 64)).astype(np.float32))
    out, us = timed(lambda: ops.flash_attention(q, k, v)
                    .block_until_ready())
    kk, vv = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
    want = ref.attention_ref(q.reshape(4, sq, 64), kk.reshape(4, sq, 64),
                             vv.reshape(4, sq, 64)).reshape(1, 4, sq, 64)
    err = float(np.abs(np.asarray(out) - np.asarray(want)).max())
    lines.append(emit("kernel/flash_attention", us, f"maxerr={err:.2e}"))

    bh, l, p, nst = 2, 256, 16, 8
    x = jnp.asarray(rng.standard_normal((bh, l, p)).astype(np.float32))
    dt = jnp.asarray((0.1 + rng.random((bh, l)) * 0.5).astype(np.float32))
    a = jnp.asarray((-0.5 - rng.random(bh)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((bh, l, nst)).astype(np.float32)
                    * 0.3)
    c = jnp.asarray(rng.standard_normal((bh, l, nst)).astype(np.float32)
                    * 0.3)
    out, us = timed(lambda: ops.ssd_scan(x, dt, a, b, c, chunk=128)
                    .block_until_ready())
    err = float(np.abs(np.asarray(out)
                       - np.asarray(ref.ssd_ref(x, dt, a, b, c))).max())
    lines.append(emit("kernel/ssd_scan", us, f"maxerr={err:.2e}"))
    return lines
