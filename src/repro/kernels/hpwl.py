"""Pallas kernel: per-net pin bounding boxes, and HPWL from them.

The detailed-placement annealer (§3.4, Eq. 2) evaluates batches of
candidate moves; each evaluation reduces every net's pin bounding box. In
dense form the net pins are padded to (n_nets, K, 2) with +/- sentinel
coordinates, and the kernel is a pure VPU reduction, tiled over nets —
the ideal TPU shape for this workload (no scatter, no host sync).

One kernel, two entry points:

* ``net_bboxes`` — per-net (xmin, xmax, ymin, ymax) boxes, which the
  device-resident annealer keeps as chain state (the overlap term
  gathers an occupancy integral image at box corners).
* ``hpwl`` — per-net half-perimeter wirelength, the Eq. 2 distance term,
  reduced from those boxes. (A kernel of its own, with a 1-D output
  block, is refused by Mosaic once the padded net axis outgrows one
  block: XLA tiles the 1-D output differently from the kernel.)

The kernel compiles for TPU v5e at 1024 nets
(``tests/test_tpu_compile.py``).

``interpret`` resolves per call from the active backend (compiled on
TPU, interpret elsewhere — CPU has no Mosaic backend), exactly like
``minplus``; pass an explicit bool to pin it.

Validated against ``ref.hpwl_ref`` / ``ref.net_bboxes_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .minplus import _default_interpret

BLOCK_NETS = 256
SENTINEL = 1 << 20


def _bbox_block(pins, mask):
    """(BN, K, 2) pins + (BN, K) bool mask -> four (BN,) box edges."""
    big = jnp.int32(SENTINEL)
    x = pins[:, :, 0]
    y = pins[:, :, 1]
    xmax = jnp.max(jnp.where(mask, x, -big), axis=1)
    xmin = jnp.min(jnp.where(mask, x, big), axis=1)
    ymax = jnp.max(jnp.where(mask, y, -big), axis=1)
    ymin = jnp.min(jnp.where(mask, y, big), axis=1)
    return xmin, xmax, ymin, ymax


def _bbox_kernel(pins_ref, mask_ref, out_ref):
    """pins: (BN, K, 2) int32; mask: (BN, K) int32; out (BN, 4) int32 as
    (xmin, xmax, ymin, ymax); empty nets collapse to the zero box."""
    mask = mask_ref[...] > 0
    xmin, xmax, ymin, ymax = _bbox_block(pins_ref[...], mask)
    any_pin = jnp.any(mask, axis=1)
    box = jnp.stack([xmin, xmax, ymin, ymax], axis=1)
    out_ref[...] = jnp.where(any_pin[:, None], box, 0)


def _pad_nets(pins, mask):
    n = pins.shape[0]
    n_pad = pl.cdiv(n, BLOCK_NETS) * BLOCK_NETS
    pins_p = jnp.pad(pins, ((0, n_pad - n), (0, 0), (0, 0)))
    mask_p = jnp.pad(mask.astype(jnp.int32), ((0, n_pad - n), (0, 0)))
    return pins_p, mask_p, n_pad


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bbox_jit(pins, mask, interpret: bool) -> jnp.ndarray:
    n, k, _ = pins.shape
    pins_p, mask_p, n_pad = _pad_nets(pins, mask)
    out = pl.pallas_call(
        _bbox_kernel,
        grid=(n_pad // BLOCK_NETS,),
        in_specs=[
            pl.BlockSpec((BLOCK_NETS, k, 2), lambda i: (i, 0, 0)),
            pl.BlockSpec((BLOCK_NETS, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_NETS, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 4), jnp.int32),
        interpret=interpret,
    )(pins_p, mask_p)
    return out[:n]


def net_bboxes(pins: jnp.ndarray, mask: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-net bounding boxes (n_nets, 4) int32 as (xmin, xmax, ymin,
    ymax); a net with no live pins is the zero box.

    ``interpret=None`` resolves from the backend *before* the jit
    boundary (the jit cache keys on the resolved bool): compiled on
    TPU, interpret mode everywhere else."""
    if interpret is None:
        interpret = _default_interpret()
    return _bbox_jit(pins, mask, interpret)


def hpwl(pins: jnp.ndarray, mask: jnp.ndarray,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """pins: (n_nets, K, 2) int32 padded pin coords; mask: (n_nets, K).
    Returns per-net HPWL (n_nets,) int32, 0 for an empty net. Same
    backend-resolved ``interpret`` contract as :func:`net_bboxes`."""
    box = net_bboxes(pins, mask, interpret=interpret)
    return (box[:, 1] - box[:, 0]) + (box[:, 3] - box[:, 2])


def pack_nets(pin_net, pin_xy, n_nets: int, k_max: int):
    """Host-side helper: (pin_net, pin_xy) lists -> dense (n_nets, K, 2)."""
    import numpy as np
    pins = np.zeros((n_nets, k_max, 2), np.int32)
    mask = np.zeros((n_nets, k_max), np.int32)
    fill = np.zeros(n_nets, np.int32)
    for net, (x, y) in zip(pin_net, pin_xy):
        j = fill[net]
        if j >= k_max:
            raise ValueError(f"net {net} exceeds K={k_max} pins")
        pins[net, j] = (x, y)
        mask[net, j] = 1
        fill[net] += 1
    return pins, mask
