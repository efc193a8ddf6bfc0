"""Static backend: structural verification, config sweep, route delivery."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.edsl import create_uniform_interconnect
from repro.core.graph import IO, NodeKind, Side
from repro.core.lowering import compile_interconnect
from repro.core.verify import verify, verify_structural


@pytest.fixture(scope="module")
def small_ic():
    return create_uniform_interconnect(width=4, height=4, num_tracks=2,
                                       sb_type="wilton", io_ring=True,
                                       reg_density=1.0)


@pytest.fixture(scope="module")
def fabric(small_ic):
    return compile_interconnect(small_ic)


def test_structural_equivalence(small_ic, fabric):
    verify_structural(small_ic, fabric)


def test_config_sweep(small_ic, fabric):
    report = verify(small_ic, fabric)
    assert report["connections_checked"] > 500


def manual_east_route(ic, y=1, track=0):
    g = ic.graph(16)
    edges = []
    port = g.get_port(0, y, "io_out")
    sb_out = g.get_sb(0, y, Side.EAST, track, IO.SB_OUT)
    edges.append((port, sb_out))
    cur = sb_out
    w = ic.dims()[0]
    for x in range(1, w):
        rmux = [n for n in cur.fan_out if n.kind == NodeKind.REG_MUX][0]
        reg = [n for n in cur.fan_out if n.kind == NodeKind.REGISTER][0]
        edges += [(cur, reg), (reg, rmux)]
        sb_in = rmux.fan_out[0]
        edges.append((rmux, sb_in))
        if x < w - 1:
            nxt = g.get_sb(x, y, Side.EAST, track, IO.SB_OUT)
            edges.append((sb_in, nxt))
            cur = nxt
        else:
            edges.append((sb_in, g.get_port(x, y, "io_in")))
    return edges


def test_registered_route_delivers_with_latency(small_ic, fabric):
    edges = manual_east_route(small_ic)
    config = jnp.asarray(fabric.route_to_config(edges))
    io_idx = {c: i for i, c in enumerate(fabric.io_coords)}
    T = 10
    ext = np.zeros((T, fabric.num_io), np.int32)
    ext[:, io_idx[(0, 1)]] = np.arange(100, 100 + T)
    out = np.asarray(fabric.run(config, jnp.asarray(ext), depth=12))
    got = out[:, io_idx[(3, 1)]]
    lat = np.nonzero(got)[0][0]
    assert lat == 3                       # one register per hop
    assert list(got[lat:]) == list(range(100, 100 + T - lat))


def test_conflicting_route_rejected(small_ic, fabric):
    edges = manual_east_route(small_ic)
    g = small_ic.graph(16)
    # drive the same SB_OUT from a second source: conflicting mux select
    sb_out = g.get_sb(0, 1, Side.EAST, 0, IO.SB_OUT)
    other_src = [n for n in sb_out.fan_in
                 if n is not edges[0][0]][0]
    with pytest.raises(ValueError, match="conflict"):
        fabric.route_to_config(edges + [(other_src, sb_out)])


def test_run_batch_lane_equals_run_on_manual_route(small_ic, fabric):
    """Three registered routes (two rows, two tracks) through one
    run_batch: each lane equals its own run, and the routed output
    carries its input three cycles late (one register per hop)."""
    routes = [((0, 1), manual_east_route(small_ic, y=1)),
              ((0, 2), manual_east_route(small_ic, y=2)),
              ((0, 1), manual_east_route(small_ic, y=1, track=1))]
    io_idx = {c: i for i, c in enumerate(fabric.io_coords)}
    T = 8
    configs = np.stack([fabric.route_to_config(e) for _, e in routes])
    ext = np.zeros((len(routes), T, fabric.num_io), np.int32)
    for b, (src, _) in enumerate(routes):
        ext[b, :, io_idx[src]] = np.arange(10 * b + 7, 10 * b + 7 + T)
    out = np.asarray(fabric.run_batch(jnp.asarray(configs),
                                      jnp.asarray(ext), depth=12))
    for b, (src, _) in enumerate(routes):
        single = np.asarray(fabric.run(jnp.asarray(configs[b]),
                                       jnp.asarray(ext[b]), depth=12))
        np.testing.assert_array_equal(out[b], single)
        want = [0, 0, 0] + list(range(10 * b + 7, 10 * b + 7 + T - 3))
        assert list(out[b, :, io_idx[(3, src[1])]]) == want
