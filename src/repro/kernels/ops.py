"""Public jit'd wrappers around the Pallas kernels.

Off TPU the kernels run in interpret mode; on TPU ``pallas_call`` lowers
through Mosaic. ``interpret`` is resolved from the backend on every call,
so a mid-process platform swap (tests forcing ``jax.default_backend``)
picks the right mode.

Mosaic compiles ``minplus_*``, ``hpwl`` and ``net_bboxes`` for TPU v5e
(``tests/test_tpu_compile.py``). The fabric's emulation engine is XLA
(``fabric_chain``) and has no wrapper here.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from . import flash_attention as _flash
from . import hpwl as _hpwl
from . import minplus as _minplus
from . import ssd_scan as _ssd
from .minplus import _default_interpret as _interpret


def hpwl(pins: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    return _hpwl.hpwl(pins, mask, interpret=_interpret())


def net_bboxes(pins: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Per-net (xmin, xmax, ymin, ymax) pin bounding boxes."""
    return _hpwl.net_bboxes(pins, mask, interpret=_interpret())


def minplus_step(d: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return _minplus.minplus_step(d, w, interpret=_interpret())


def minplus_fixpoint(d0: jnp.ndarray, w: jnp.ndarray,
                     iters: int) -> jnp.ndarray:
    return _minplus.minplus_fixpoint(d0, w, iters, interpret=_interpret())


def minplus_wavefront(d0: jnp.ndarray, w: jnp.ndarray,
                      engine: str = "auto") -> jnp.ndarray:
    """Converged batched shortest-path cost fields (the router's batched
    wavefront engine): Pallas kernel on TPU, jitted dense reference
    elsewhere."""
    return _minplus.minplus_wavefront(d0, w, engine=engine,
                                      interpret=_interpret())


def minplus_wavefront_blocks(d0: jnp.ndarray, w: jnp.ndarray
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`minplus_wavefront`'s field and the number of relaxation
    blocks it took, both on the device."""
    return _minplus.minplus_wavefront_blocks(d0, w, interpret=_interpret())


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True) -> jnp.ndarray:
    """GQA-aware wrapper. q: (B, Hq, S, D); k/v: (B, Hkv, S, D)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hq, -1, d)
    vf = v.reshape(b * hq, -1, d)
    out = _flash.flash_attention(qf, kf, vf, causal=causal,
                                 interpret=_interpret())
    return out.reshape(b, hq, sq, d)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, chunk: int = 128
             ) -> jnp.ndarray:
    return _ssd.ssd_scan(x, dt, a, b, c, chunk=chunk,
                         interpret=_interpret())
