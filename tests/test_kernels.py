"""Per-kernel interpret-mode validation against the pure-jnp oracles,
sweeping shapes and dtypes (assignment requirement)."""
import functools

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.core.pnr import reference
from repro.core.tiles import PECore
from repro.kernels import fabric_chain, ops, ref
from repro.kernels.fabric_chain import PE_OPS

WORD = 0xFFFF


def _alu_operands():
    """Random 16-bit words, plus 0, 0xFFFF and the shift amounts 15, 16,
    17 and 0xFFFF crossed with every corner of ``a``."""
    rng = np.random.default_rng(14)
    corners = np.array([0, 1, 15, 16, 17, 0x8000, WORD], np.int64)
    a, b = (x.ravel() for x in np.meshgrid(corners, corners))
    n = 256
    a = np.concatenate([a, rng.integers(0, WORD + 1, n)])
    b = np.concatenate([b, rng.integers(0, WORD + 1, n)])
    c = rng.integers(0, WORD + 1, len(a))
    const = rng.integers(0, WORD + 1, len(a))
    return a, b, c, const


@pytest.mark.parametrize("op", PE_OPS)
def test_pe_alu_matches_reference(op):
    """The device ALU (``pe_alu_candidates``) equals the plain app
    reference's ``_alu`` and ``PECore.evaluate`` word for word; shifts
    take the amount's low four bits."""
    a, b, c, const = _alu_operands()
    cand = fabric_chain.pe_alu_candidates(
        *(jnp.asarray(x, jnp.int32) for x in (a, b, c, const)))
    got = np.asarray(cand[PE_OPS.index(op)]) & WORD
    np.testing.assert_array_equal(got, reference._alu(op, a, b, c, const),
                                  err_msg=op)
    want = [PECore.evaluate(op, [int(x), int(y), int(z)], int(k))
            for x, y, z, k in zip(a, b, c, const)]
    np.testing.assert_array_equal(got, want, err_msg=op)


def _chain_case(n, f, b, seed):
    """Random chain-engine tables over ``n`` nodes (sentinel ``n``): a
    functional graph of selected inputs, cyclic in places (nodes 0 and
    1 always select each other), roots, PE result and pinned-state
    indices, every node read out."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n + 1, (n, f))
    src[0], src[1] = 1, 0
    sel = rng.integers(0, f, (b, n))
    root = np.append(rng.random(n) < 0.1, True)
    root[:2] = False
    p = 8
    res = np.where(root & (rng.random(n + 1) < 0.5),
                   rng.integers(0, 2 * p, n + 1), 2 * p)
    res[n] = 2 * p
    pin_src = rng.integers(0, 40, n + 1)
    read = rng.permutation(n)
    return src, sel, root, res, pin_src, read


def _chain_tables(src, sel, root, res, pin_src, read, max_depth):
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    out = fabric_chain.chain_tables(i32(sel), i32(src), i32(root), i32(res),
                                    i32(pin_src), i32(read), max_depth)
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("n,f", [(64, 2), (700, 6), (1500, 9)])
def test_chain_tables_match_host_walk(n, f):
    """Each read-out node's hop count and root equal a host walk along
    the selected inputs: the hops to the first root, capped at
    ``max_depth + 1`` (also where the walk cycles and never reaches a
    root); and, within the cap, the root's PE result index and pinned
    state index."""
    b, max_depth = 3, f + 1
    src, sel, root, res, pin_src, read = _chain_case(n, f, b, n)
    got = _chain_tables(src, sel, root, res, pin_src, read, max_depth)
    cap = max_depth + 1
    for lane in range(b):
        for k, j in enumerate(read):
            node, hops = int(j), 0
            while not root[node] and hops <= cap:
                node, hops = int(src[node, sel[lane, node]]), hops + 1
            assert got["hop"][lane, k] == min(hops, cap), (lane, j)
            if hops <= cap:
                assert got["res"][lane, k] == res[node], (lane, j)
                assert got["cidx"][lane, k] == pin_src[node], (lane, j)
    hop = got["hop"]
    assert (hop == cap).any() and (hop == 0).any()
    assert ((hop > 0) & (hop < cap)).any()
    assert (hop[:, np.isin(read, (0, 1))] == cap).all()


@pytest.mark.parametrize("b", [1, 5, 9])
def test_chain_tables_lanes_independent(b):
    """A lane's tables depend only on its own selects: a batch of ``b``
    lanes equals each lane built alone."""
    src, sel, root, res, pin_src, read = _chain_case(300, 4, b, b)
    both = _chain_tables(src, sel, root, res, pin_src, read, 9)
    for lane in range(b):
        alone = _chain_tables(src, sel[lane:lane + 1], root, res, pin_src,
                              read, 9)
        for k in both:
            np.testing.assert_array_equal(both[k][lane], alone[k][0],
                                          err_msg=f"{k} lane {lane}")


@given(st.integers(1, 400), st.integers(1, 9), st.integers(0, 2**31 - 1))
@settings(max_examples=12, deadline=None)
def test_hpwl_property(n_nets, k, seed):
    rng = np.random.default_rng(seed)
    pins = jnp.asarray(rng.integers(0, 64, (n_nets, k, 2))
                       .astype(np.int32))
    mask = jnp.asarray((rng.random((n_nets, k)) < 0.7).astype(np.int32))
    got = np.asarray(ops.hpwl(pins, mask))
    want = np.asarray(ref.hpwl_ref(pins, mask))
    np.testing.assert_array_equal(got, want)
    assert (got >= 0).all()


@given(st.integers(1, 300), st.integers(1, 8), st.integers(0, 2**31 - 1))
@settings(max_examples=12, deadline=None)
def test_net_bboxes_property(n_nets, k, seed):
    rng = np.random.default_rng(seed)
    pins = jnp.asarray(rng.integers(0, 64, (n_nets, k, 2))
                       .astype(np.int32))
    # sparse mask so fully-empty rows actually occur
    mask = jnp.asarray((rng.random((n_nets, k)) < 0.5).astype(np.int32))
    got = np.asarray(ops.net_bboxes(pins, mask))
    want = np.asarray(ref.net_bboxes_ref(pins, mask))
    np.testing.assert_array_equal(got, want)
    # bbox spans reproduce the HPWL kernel's reduction
    span = (got[:, 1] - got[:, 0]) + (got[:, 3] - got[:, 2])
    np.testing.assert_array_equal(span, np.asarray(ops.hpwl(pins, mask)))


def test_hpwl_empty_net_rows():
    """All-masked rows contribute zero HPWL and a zero bbox."""
    pins = jnp.asarray(np.arange(3 * 4 * 2, dtype=np.int32)
                       .reshape(3, 4, 2))
    mask = jnp.asarray(np.array([[1, 1, 0, 0],
                                 [0, 0, 0, 0],
                                 [1, 0, 1, 1]], np.int32))
    got = np.asarray(ops.hpwl(pins, mask))
    assert got[1] == 0
    np.testing.assert_array_equal(got, np.asarray(ref.hpwl_ref(pins, mask)))
    boxes = np.asarray(ops.net_bboxes(pins, mask))
    np.testing.assert_array_equal(boxes[1], np.zeros(4, np.int32))


def test_pack_nets_overflow():
    from repro.kernels.hpwl import pack_nets

    pin_net = [0, 0, 0]
    pin_xy = [(0, 0), (1, 1), (2, 2)]
    pins, mask = pack_nets(pin_net, pin_xy, n_nets=1, k_max=4)
    assert pins.shape == (1, 4, 2) and int(mask.sum()) == 3
    with pytest.raises(ValueError, match="exceeds"):
        pack_nets(pin_net, pin_xy, n_nets=1, k_max=2)


@pytest.mark.parametrize("n,b", [(64, 1), (200, 4), (300, 2), (130, 300)])
def test_minplus(n, b):
    rng = np.random.default_rng(n + b)
    d = jnp.asarray((rng.random((b, n)) * 10).astype(np.float32))
    w = np.where(rng.random((n, n)) < 0.05, rng.random((n, n)) * 3, 1e30)
    np.fill_diagonal(w, 0.0)
    w = jnp.asarray(w.astype(np.float32))
    np.testing.assert_allclose(np.asarray(ops.minplus_step(d, w)),
                               np.asarray(ref.minplus_ref(d, w)),
                               rtol=1e-5)


def test_minplus_batch_blocks_leave_rows_unchanged():
    """A batch over BATCH_BLOCK rows is gridded in row blocks; every row
    still equals the same row relaxed on its own, bit for bit."""
    from repro.kernels.minplus import BATCH_BLOCK

    n, b = 130, BATCH_BLOCK + 44
    rng = np.random.default_rng(3)
    d = (rng.random((b, n)) * 10).astype(np.float32)
    w = np.where(rng.random((n, n)) < 0.05, rng.random((n, n)) * 3, 1e30)
    np.fill_diagonal(w, 0.0)
    w = jnp.asarray(w.astype(np.float32))
    whole = np.asarray(ops.minplus_step(jnp.asarray(d), w))
    for row in (0, BATCH_BLOCK - 1, BATCH_BLOCK, b - 1):
        alone = np.asarray(ops.minplus_step(jnp.asarray(d[row:row + 1]), w))
        np.testing.assert_array_equal(whole[row], alone[0])


def test_minplus_fixpoint_is_shortest_path():
    """Iterated relaxation on a line graph gives hop-count distances."""
    n = 16
    w = np.full((n, n), 1e30, np.float32)
    np.fill_diagonal(w, 0.0)
    for i in range(n - 1):
        w[i, i + 1] = 1.0
    d0 = np.full((1, n), 1e30, np.float32)
    d0[0, 0] = 0.0
    out = np.asarray(ops.minplus_fixpoint(jnp.asarray(d0),
                                          jnp.asarray(w), n))
    np.testing.assert_allclose(out[0], np.arange(n, dtype=np.float32))


@pytest.mark.parametrize("engine", ["pallas", "ref"])
def test_minplus_wavefront_converges_to_bellman_ford(engine):
    """The adaptive wavefront (early-exit blocks) equals the full
    Bellman-Ford bound on a random sparse graph, on both engines."""
    from repro.kernels.minplus import minplus_wavefront

    n, b = 96, 3
    rng = np.random.default_rng(7)
    w = np.where(rng.random((n, n)) < 0.06, rng.random((n, n)) * 3 + 0.1,
                 3e37).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    d0 = np.full((b, n), 3e37, np.float32)
    d0[np.arange(b), [0, 5, 11]] = 0.0
    got = np.asarray(minplus_wavefront(jnp.asarray(d0), jnp.asarray(w),
                                       engine=engine, interpret=True))
    want = np.asarray(ref.minplus_fixpoint_ref(jnp.asarray(d0),
                                               jnp.asarray(w), n - 1))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def _host_wavefront(d0, w, block_iters=8):
    """Host oracle of the block loop: relax in blocks of ``block_iters``
    on the host, stop after the first block that leaves the field
    unchanged or at ``ceil((N-1)/block_iters)`` blocks. Returns the
    field and the blocks run."""
    cap = max(1, -(-max(w.shape[0] - 1, 1) // block_iters))
    d = d0
    for blocks in range(1, cap + 1):
        nd = d
        for _ in range(block_iters):
            nd = np.minimum(nd, np.min(nd[:, :, None] + w[None], axis=1))
        if np.array_equal(nd, d):
            return nd, blocks
        d = nd
    return d, cap


def _sparse_graph():
    n, b = 96, 4
    rng = np.random.default_rng(7)
    w = np.where(rng.random((n, n)) < 0.06, rng.random((n, n)) * 3 + 0.1,
                 3e37).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    d0 = np.full((b, n), 3e37, np.float32)
    d0[np.arange(3), [0, 5, 11]] = 0.0          # row 3: an all-INF pad lane
    return d0, w


def _line_graph():
    """A path 0 -> 1 -> ... -> 24: the far end is reached at relaxation
    N - 1 = 24, so every block changes the field and the cap ends it."""
    n = 25
    w = np.full((n, n), 3e37, np.float32)
    np.fill_diagonal(w, 0.0)
    w[np.arange(n - 1), np.arange(1, n)] = 1.0
    d0 = np.full((2, n), 3e37, np.float32)
    d0[0, 0] = 0.0
    return d0, w


@pytest.mark.parametrize("graph", [_sparse_graph, _line_graph],
                         ids=["sparse", "line"])
@pytest.mark.parametrize("engine", ["pallas", "ref"])
def test_minplus_wavefront_equals_host_block_loop(engine, graph):
    """The device block loop returns the host oracle's field bit for bit
    and runs as many blocks."""
    from repro.kernels.minplus import minplus_wavefront_blocks

    d0, w = graph()
    field, blocks = minplus_wavefront_blocks(
        jnp.asarray(d0), jnp.asarray(w), engine=engine, interpret=True)
    want, want_blocks = _host_wavefront(d0, w)
    np.testing.assert_array_equal(np.asarray(field), want)
    assert int(blocks) == want_blocks
    if graph is _line_graph:
        assert want_blocks == 3
        np.testing.assert_array_equal(want[0], np.arange(25, dtype=np.float32))


@pytest.mark.parametrize("engine", ["pallas", "ref"])
def test_minplus_wavefront_compiles_once_per_shape(engine):
    """A second and third call at the same shape reuse the first call's
    program: no new program, and no backend compile."""
    from repro.core import trace
    from repro.kernels.minplus import minplus_wavefront, wavefront_programs

    d0, w = _sparse_graph()
    minplus_wavefront(jnp.asarray(d0), jnp.asarray(w), engine=engine,
                      interpret=True).block_until_ready()
    programs = wavefront_programs()
    rng = np.random.default_rng(1)
    with trace.recording() as rec:
        for _ in range(2):
            w2 = np.where(w < 3e37, w * rng.random(w.shape).astype(
                np.float32), w)
            minplus_wavefront(jnp.asarray(d0), jnp.asarray(w2),
                              engine=engine,
                              interpret=True).block_until_ready()
    assert wavefront_programs() == programs
    assert rec.unattributed["jit_n"] == 0


@pytest.mark.parametrize("sq,skv,hq,hkv,dtype", [
    (128, 128, 4, 4, jnp.float32),
    (200, 200, 4, 2, jnp.float32),
    (256, 256, 8, 1, jnp.bfloat16),
    (130, 384, 2, 2, jnp.float32),
])
def test_flash_attention(sq, skv, hq, hkv, dtype):
    rng = np.random.default_rng(sq + skv)
    b, d = 2, 64
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)),
                    dtype=dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    kk = jnp.repeat(k, hq // hkv, 1)
    vv = jnp.repeat(v, hq // hkv, 1)
    want = ref.attention_ref(
        q.reshape(b * hq, sq, d).astype(jnp.float32),
        kk.reshape(b * hq, skv, d).astype(jnp.float32),
        vv.reshape(b * hq, skv, d).astype(jnp.float32),
        causal=True).reshape(b, hq, sq, d)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("l,chunk,p,n", [
    (128, 64, 8, 4), (256, 128, 16, 8), (100, 32, 4, 4),
])
def test_ssd_scan(l, chunk, p, n):
    rng = np.random.default_rng(l)
    bh = 3
    x = jnp.asarray(rng.standard_normal((bh, l, p)).astype(np.float32))
    dt = jnp.asarray((0.1 + rng.random((bh, l)) * 0.5).astype(np.float32))
    a = jnp.asarray((-0.5 - rng.random(bh)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((bh, l, n)).astype(np.float32)
                    * 0.3)
    c = jnp.asarray(rng.standard_normal((bh, l, n)).astype(np.float32)
                    * 0.3)
    out = ops.ssd_scan(x, dt, a, b, c, chunk=chunk)
    want = ref.ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_ssd_xla_path_matches_ref():
    """The models' jnp chunked SSD (used when attn_impl='xla') must match
    the naive recurrence too."""
    from repro.models.layers import _ssd_xla
    rng = np.random.default_rng(0)
    bh, l, p, n = 2, 96, 8, 4
    x = jnp.asarray(rng.standard_normal((bh, l, p)).astype(np.float32))
    dt = jnp.asarray((0.1 + rng.random((bh, l)) * 0.5).astype(np.float32))
    a = jnp.asarray((-0.5 - rng.random(bh)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((bh, l, n)).astype(np.float32)
                    * 0.3)
    c = jnp.asarray(rng.standard_normal((bh, l, n)).astype(np.float32)
                    * 0.3)
    got = _ssd_xla(x, dt, a, b, c, chunk=32)
    want = ref.ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
