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


def test_pallas_fabric_sweep_matches_xla(small_ic):
    """use_pallas=True swaps the sweep for the Pallas kernel (interpret)."""
    fab_ref = compile_interconnect(small_ic, use_pallas=False)
    fab_pal = compile_interconnect(small_ic, use_pallas=True)
    edges = manual_east_route(small_ic)
    config = jnp.asarray(fab_ref.route_to_config(edges))
    io_idx = {c: i for i, c in enumerate(fab_ref.io_coords)}
    T = 6
    ext = np.zeros((T, fab_ref.num_io), np.int32)
    ext[:, io_idx[(0, 1)]] = np.arange(7, 7 + T)
    a = np.asarray(fab_ref.run(config, jnp.asarray(ext), depth=10))
    b = np.asarray(fab_pal.run(config, jnp.asarray(ext), depth=10))
    assert np.array_equal(a, b)


def test_pallas_fabric_engine_refused_on_tpu(small_ic, monkeypatch):
    """Mosaic refuses the fabric kernels' gathers: on a TPU backend the
    Pallas engine fails at construction with an error that says why and
    names the way out, while the XLA engine builds as usual."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError,
                       match=r"Mosaic(.|\n)*use_pallas=False(.|\n)*"
                             r"Emulation engine choice"):
        compile_interconnect(small_ic, use_pallas=True)
    assert not compile_interconnect(small_ic, use_pallas=False).use_pallas

