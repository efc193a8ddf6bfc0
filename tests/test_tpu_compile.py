"""The main path's device programs compile for a TPU v5e at Amber widths.

JAX's TPU compiler compiles for a chip that is described, not attached,
so these tests need no accelerator: each one lowers a kernel (or the XLA
emulation step) from ``ShapeDtypeStruct``s placed on one chip of a
described ``v5e:2x2`` topology and compiles it, which raises what the
chip's compiler would raise (refused lowerings, scoped VMEM over the
limit). Interpret-mode tests cannot see either.

Shapes are those of ``configs/cgra_amber.FULL`` lowered: 86,288 fabric
nodes with fan-in 20, 780 PEs and 32x32 = 1024 tiles for the router's
coarse graph. The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library, and it
keeps it until it exits, so every test of this kind stays in this file.

The fabric's emulation engine is XLA, not Pallas: the chain engine and
its sweep oracle are compiled below at Amber widths.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import fabric_chain, hpwl, minplus, ref

FULL_NODES = 86_288     # cgra_amber.FULL lowered
FULL_FANIN = 20
FULL_PES = 780
FULL_TILES = 32 * 32    # the min-plus router's coarse graph
AMBER_NETS = 1024       # ~one net per placed instance at FULL
NET_PINS = 16
HBM_BYTES = 16 * 10**9  # v5e HBM per chip
#: ``canal_amber_full`` lowered: nodes, PEs, registers, memories, IOs
AMBER = dict(n=51_192, p=384, r=11_720, m=128, io=100)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    """The compiled program calls a Mosaic kernel (not interpret mode)."""
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [1, 64, 256, 1024])
def test_minplus_step_compiles(one_chip, batch):
    """Every seed batch the router buckets to, up to ``n_tiles`` rows."""
    d = _shape(one_chip, (batch, FULL_TILES), jnp.float32)
    w = _shape(one_chip, (FULL_TILES, FULL_TILES), jnp.float32)
    compiled = _compile(functools.partial(minplus.minplus_step,
                                          interpret=False), d, w)
    assert _has_kernel(compiled)


def test_minplus_fixpoint_compiles(one_chip):
    """The router's device block: ``block_iters`` relaxations in one
    program, at the largest bucket."""
    d = _shape(one_chip, (FULL_TILES, FULL_TILES), jnp.float32)
    w = _shape(one_chip, (FULL_TILES, FULL_TILES), jnp.float32)
    compiled = _compile(functools.partial(minplus.minplus_fixpoint,
                                          iters=8, interpret=False), d, w)
    assert _has_kernel(compiled)


@pytest.mark.parametrize("batch", [1, 1024])
def test_minplus_wavefront_compiles(one_chip, batch):
    """The router's whole cost-field program, the block loop and its
    convergence test included, at the smallest and largest bucket."""
    d = _shape(one_chip, (batch, FULL_TILES), jnp.float32)
    w = _shape(one_chip, (FULL_TILES, FULL_TILES), jnp.float32)
    compiled = _compile(functools.partial(minplus._wavefront, block_iters=8,
                                          use_kernel=True, interpret=False),
                        d, w)
    assert _has_kernel(compiled)


@pytest.mark.parametrize("kernel", [hpwl.hpwl, hpwl.net_bboxes],
                         ids=["hpwl", "net_bboxes"])
def test_net_box_kernels_compile(one_chip, kernel):
    pins = _shape(one_chip, (AMBER_NETS, NET_PINS, 2), jnp.int32)
    mask = _shape(one_chip, (AMBER_NETS, NET_PINS), jnp.int32)
    compiled = _compile(functools.partial(kernel, interpret=False),
                        pins, mask)
    assert _has_kernel(compiled)


def test_fused_xla_emulation_step_compiles(one_chip):
    """The sweep oracle of the emulation step, the served chain engine's
    test reference: the fused fixpoint of ``ref.fabric_fused_batch_ref``
    for 8 configurations at FULL, depth 16, fits one chip."""
    b, n, f, p = 8, FULL_NODES, FULL_FANIN, FULL_PES

    def i32(*shape):
        return _shape(one_chip, shape, jnp.int32)

    compiled = _compile(
        functools.partial(ref.fabric_fused_batch_ref, max_depth=16),
        i32(b, n), i32(b, n), i32(b, n), i32(b), i32(b, p), i32(b, p),
        i32(b, p, 4), i32(b, p, 4), i32(n, f), i32(n), i32(n), i32(p, 4),
        i32(p, 2))
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES


def test_chain_emulation_step_compiles(one_chip):
    """The served emulation step: the chain engine's per-batch tables
    and one cycle's fixpoint for the benchmark's batch on Amber's array
    (B = 5, depth 170) fit one chip."""
    b, depth, f = 5, 170, FULL_FANIN
    n, p = AMBER["n"], AMBER["p"]
    k = 4 * p + AMBER["r"] + AMBER["m"] + AMBER["io"]
    s = AMBER["r"] + AMBER["io"] + AMBER["m"] + 1

    def i32(*shape):
        return _shape(one_chip, shape, jnp.int32)

    def cycle(sel, src, root, res, pin_src, read, pin_state, depths, op,
              const, imm_mask, imm_val):
        chains = fabric_chain.chain_tables(sel, src, root, res, pin_src,
                                           read, depth)
        return fabric_chain.chain_fixpoint(
            chains, pin_state, depths, op, const, imm_mask, imm_val,
            max_depth=depth)

    compiled = _compile(
        cycle, i32(b, n), i32(n, f), i32(n + 1), i32(n + 1), i32(n + 1),
        i32(k), i32(b, s), i32(b), i32(b, p), i32(b, p), i32(b, p, 4),
        i32(b, p, 4))
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES
