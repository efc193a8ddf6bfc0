"""Differential harness for the fabric emulators: the fused batched
engine must be bit-identical to the serial per-config reference.

Hypothesis-driven (through ``tests/_hypothesis_compat``): random
interconnect geometries, random (often combinationally-cyclic) configs,
random PE programs and stream lengths, checked on both the vmap oracle
path (``use_pallas=False``) and the Pallas interpret path
(``use_pallas=True``), and — in a subprocess with forced host devices —
on the shard_map multi-device path.

The served XLA engine, the chain engine (``kernels/fabric_chain.py``),
is also checked against the N-wide sweep oracle
``ref.fabric_fused_batch_ref``: on every node when called directly, and
on every read-out of ``step_batch`` over several cycles."""
import functools
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

import canal
from repro.core.edsl import create_uniform_interconnect
from repro.core.lowering import WORD, compile_interconnect
from repro.kernels import fabric_chain
from repro.kernels import ref as kref

SRC_ROOT = os.path.join(os.path.dirname(__file__), "..", "src")


@functools.lru_cache(maxsize=None)
def _ic(width, height, num_tracks):
    return create_uniform_interconnect(width=width, height=height,
                                       num_tracks=num_tracks,
                                       sb_type="wilton", io_ring=True,
                                       reg_density=1.0)


@functools.lru_cache(maxsize=None)
def _fabric(width, height, num_tracks, use_pallas):
    return compile_interconnect(_ic(width, height, num_tracks),
                                use_pallas=use_pallas)


def _random_workload(fab, rng, batch, cycles):
    """Random configs (legal and cycle-wiring alike), IO streams and PE
    programs — the full surface run/run_batch must agree on."""
    cfgs = rng.integers(0, 4, (batch, fab.num_config)).astype(np.int32)
    ext = rng.integers(0, 2000, (batch, cycles, fab.num_io)) \
             .astype(np.int32)
    n = max(fab.num_pe, 1)
    pe_cfgs = {
        "op": rng.integers(0, 14, (batch, n)).astype(np.int32),
        "const": rng.integers(0, 0xFFFF, (batch, n)).astype(np.int32),
        "imm_mask": (rng.random((batch, n, 4)) < 0.2).astype(np.int32),
        "imm_val": rng.integers(0, 0xFFFF, (batch, n, 4))
                      .astype(np.int32),
    }
    return cfgs, ext, pe_cfgs


@pytest.mark.parametrize("use_pallas", [False, True])
@given(st.integers(3, 4), st.integers(1, 4), st.sampled_from([3, 5, 7]),
       st.integers(0, 2**31 - 1))
@settings(max_examples=4, deadline=None)
def test_run_batch_bit_identical_to_serial(use_pallas, size, batch,
                                           cycles, seed):
    """run_batch (fused and unfused) == per-config run, lane for lane,
    with per-config combinational depths — even for random configs whose
    active network is cyclic, thanks to masked early exit."""
    fab = _fabric(size, size, 2, use_pallas)
    rng = np.random.default_rng(seed)
    cfgs, ext, pe_cfgs = _random_workload(fab, rng, batch, cycles)
    serial = np.stack([
        np.asarray(fab.run(
            jnp.asarray(cfgs[i]), jnp.asarray(ext[i]),
            pe_cfg={k: jnp.asarray(v[i]) for k, v in pe_cfgs.items()}))
        for i in range(batch)])
    for fused in (True, False):
        batched = np.asarray(fab.run_batch(
            jnp.asarray(cfgs), jnp.asarray(ext),
            pe_cfgs={k: jnp.asarray(v) for k, v in pe_cfgs.items()},
            fused=fused))
        np.testing.assert_array_equal(
            serial, batched,
            err_msg=f"use_pallas={use_pallas} fused={fused} seed={seed}")


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=3, deadline=None)
def test_pallas_and_vmap_paths_agree(seed):
    """The Pallas-interpret engine and the pure-jnp oracle engine produce
    the same observations for the same workload."""
    rng = np.random.default_rng(seed)
    batch, cycles = 3, 5
    fab_ref = _fabric(4, 4, 2, False)
    fab_pal = _fabric(4, 4, 2, True)
    cfgs, ext, pe_cfgs = _random_workload(fab_ref, rng, batch, cycles)
    kw = dict(pe_cfgs={k: jnp.asarray(v) for k, v in pe_cfgs.items()})
    a = np.asarray(fab_ref.run_batch(jnp.asarray(cfgs), jnp.asarray(ext),
                                     **kw))
    b = np.asarray(fab_pal.run_batch(jnp.asarray(cfgs), jnp.asarray(ext),
                                     **kw))
    np.testing.assert_array_equal(a, b, err_msg=f"seed={seed}")


@given(st.integers(1, 64), st.integers(0, 2**31 - 1))
@settings(max_examples=4, deadline=None)
def test_stream_length_invariance(prefix, seed):
    """Emulating T cycles then truncating == emulating the first T' < T
    cycles directly: the scan carries no hidden cross-cycle coupling."""
    fab = _fabric(4, 4, 2, False)
    rng = np.random.default_rng(seed)
    cycles = 8
    cfgs, ext, pe_cfgs = _random_workload(fab, rng, 2, cycles)
    t_cut = 1 + prefix % (cycles - 1)
    kw = dict(pe_cfgs={k: jnp.asarray(v) for k, v in pe_cfgs.items()})
    full = np.asarray(fab.run_batch(jnp.asarray(cfgs), jnp.asarray(ext),
                                    **kw))
    short = np.asarray(fab.run_batch(jnp.asarray(cfgs),
                                     jnp.asarray(ext[:, :t_cut]), **kw))
    np.testing.assert_array_equal(full[:, :t_cut], short,
                                  err_msg=f"t_cut={t_cut} seed={seed}")


def test_per_lane_depth_equals_per_config_runs():
    """Explicit heterogeneous depths: lane i must behave exactly like a
    serial run at depth_i, not at the batch max."""
    fab = _fabric(4, 4, 2, False)
    rng = np.random.default_rng(7)
    cfgs, ext, pe_cfgs = _random_workload(fab, rng, 4, 5)
    depths = np.array([2, 5, 9, 3], np.int32)
    batched = np.asarray(fab.run_batch(
        jnp.asarray(cfgs), jnp.asarray(ext),
        pe_cfgs={k: jnp.asarray(v) for k, v in pe_cfgs.items()},
        depth=depths))
    serial = np.stack([
        np.asarray(fab.run(
            jnp.asarray(cfgs[i]), jnp.asarray(ext[i]),
            pe_cfg={k: jnp.asarray(v[i]) for k, v in pe_cfgs.items()},
            depth=int(depths[i])))
        for i in range(4)])
    np.testing.assert_array_equal(serial, batched)


def test_sharded_run_batch_matches_single_device():
    """shard_map over forced host devices == the single-device engine,
    including a batch that does not divide the device count (padding)."""
    code = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "from repro.core.edsl import create_uniform_interconnect\n"
        "from repro.core.lowering import compile_interconnect\n"
        "assert len(jax.devices()) == 4, jax.devices()\n"
        "ic = create_uniform_interconnect(width=3, height=3,"
        " num_tracks=2, sb_type='wilton', io_ring=True, reg_density=1.0)\n"
        "fab = compile_interconnect(ic, use_pallas=False)\n"
        "rng = np.random.default_rng(0)\n"
        "cfgs = rng.integers(0, 4, (6, fab.num_config)).astype(np.int32)\n"
        "ext = rng.integers(0, 999, (6, 4, fab.num_io)).astype(np.int32)\n"
        "one = np.asarray(fab.run_batch(jnp.asarray(cfgs),"
        " jnp.asarray(ext), shard=False))\n"
        "many = np.asarray(fab.run_batch(jnp.asarray(cfgs),"
        " jnp.asarray(ext), shard=True))\n"
        "assert np.array_equal(one, many)\n"
        "print('SHARDED_OK')\n")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = SRC_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in out.stdout, out.stderr[-2000:]


# ----------------------------------------- chain engine vs the sweep oracle
#: (fabric, configs, PE program, depths) per case: random and often
#: cyclic selects; lane depths of 0, 1 and shorter than the longest
#: chain; selects that feed PE outputs back into PE inputs on every
#: lane; immediates; delayed ports; a fabric with memory tiles
CHAIN_CASES = {
    "cyclic": dict(size=4, tracks=3, cfg="random", depths=None),
    "shallow": dict(size=4, tracks=2, cfg="random", depths=(0, 1, 2, 5)),
    "pe_feedback": dict(size=4, tracks=2, cfg="feedback", depths=None),
    "immediates": dict(size=4, tracks=2, cfg="random", depths=None,
                       imm=0.5),
    "delayed": dict(size=4, tracks=3, cfg="random", depths=None,
                    delay=0.3),
    "memory": dict(size=5, tracks=3, cfg="random", depths=None,
                   mem_columns=(2,)),
}
CHAIN_LANES, CHAIN_DEPTH, CHAIN_CYCLES = 4, 24, 3


@functools.lru_cache(maxsize=None)
def _mem_fabric(size, tracks, mem_columns):
    spec = canal.InterconnectSpec(width=size, height=size, num_tracks=tracks,
                                  sb_type="wilton", io_ring=True,
                                  reg_density=1.0, mem_columns=mem_columns)
    return compile_interconnect(canal.compile(spec,
                                              analyze="off").interconnect)


def _chain_case(name):
    """A case's fabric and a random (B-lane) cycle: configs, state, IO
    drive, PE program, depths and the static sweep bound."""
    c = CHAIN_CASES[name]
    if "mem_columns" in c:
        fab = _mem_fabric(c["size"], c["tracks"], c["mem_columns"])
        assert fab.num_mem > 0
    else:
        fab = _fabric(c["size"], c["size"], c["tracks"], False)
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    b, n = CHAIN_LANES, max(fab.num_pe, 1)
    p = fab.fused_tables["num_pe_slots"]
    cfgs = rng.integers(0, 4, (b, fab.num_config))
    if c["cfg"] == "feedback":           # keep lanes whose PEs feed PEs
        draw = rng.integers(0, 4, (8 * b, fab.num_config))
        res = np.asarray(fab.chain_tables(jnp.asarray(draw, jnp.int32),
                                          CHAIN_DEPTH)["res"])[:, :4 * p]
        cfgs = draw[(res < 2 * p).any(axis=1)][:b]
        assert len(cfgs) == b
    pe_cfg = {
        "op": rng.integers(0, 14, (b, n)),
        "const": rng.integers(0, WORD + 1, (b, n)),
        "imm_mask": rng.random((b, n, 4)) < c.get("imm", 0.0),
        "imm_val": rng.integers(0, WORD + 1, (b, n, 4)),
        "reg_mask": rng.random((b, n, 4)) < c.get("delay", 0.0),
    }
    state = {
        "regs": rng.integers(0, WORD + 1, (b, len(fab.arrays.reg_ids))),
        "mem": rng.integers(0, WORD + 1, (b, max(fab.num_mem, 1))),
        "pe_regs": rng.integers(0, WORD + 1, (b, p, 4)),
    }
    depths = (np.array(c["depths"]) if c["depths"] is not None
              else rng.integers(0, CHAIN_DEPTH + 1, b))

    def i32(d):
        return {k: jnp.asarray(v, jnp.int32) for k, v in d.items()}

    return (fab, jnp.asarray(cfgs, jnp.int32), i32(state), i32(pe_cfg),
            jnp.asarray(depths, jnp.int32), rng)


def _ext_in(fab, rng):
    return jnp.asarray(rng.integers(0, WORD + 1, (CHAIN_LANES, fab.num_io)),
                       jnp.int32)


def _pinned(fab, state, ext_in):
    """The pinned background, built by scatter as the sweep engines do."""
    a = fab.arrays
    v = jnp.zeros((ext_in.shape[0], a.num_nodes), jnp.int32)
    v = v.at[:, a.reg_ids].set(state["regs"])
    v = v.at[:, fab.io_in_nodes].set(ext_in)
    return v.at[:, fab.mem_out].set(state["mem"][:, :fab.num_mem])


def _oracle_step(fab, state, ext_in, cfgs, pe_cfg, depths):
    """One cycle of ``step_batch`` with the sweep oracle as its engine."""
    a, t = fab.arrays, fab.fused_tables
    b = cfgs.shape[0]
    op, const, imm_mask, imm_val = fab._norm_pe_cfg(pe_cfg, b,
                                                    state["pe_regs"])
    pin_vals = _pinned(fab, state, ext_in)
    vals = kref.fabric_fused_batch_ref(
        pin_vals, jax.vmap(fab._selects)(cfgs), pin_vals, depths, op, const,
        imm_mask, imm_val, jnp.asarray(a.src), jnp.asarray(t["keep"]),
        jnp.asarray(t["pin_mask"]), jnp.asarray(t["pe_in"]),
        jnp.asarray(fab.pe_out), max_depth=CHAIN_DEPTH, word=WORD)
    v = jnp.concatenate([vals, jnp.zeros((b, 1), jnp.int32)], axis=1)
    new = {"pe_regs": v[:, t["pe_in"]],
           "regs": v[:, a.reg_src],
           "mem": state["mem"].at[:, :fab.num_mem].set(v[:, fab.mem_in])}
    return new, v[:, fab.io_out_nodes]


@pytest.mark.parametrize("case", sorted(CHAIN_CASES))
def test_chain_engine_matches_sweep_oracle_on_every_node(case):
    """``fabric_chain`` called directly on a fabric's tables: every node,
    and every PE input slot, equals the sweep oracle after each lane's
    own depth."""
    fab, cfgs, state, pe_cfg, depths, rng = _chain_case(case)
    a, t = fab.arrays, fab.fused_tables
    b, n, p = CHAIN_LANES, a.num_nodes, t["num_pe_slots"]
    ext_in = _ext_in(fab, rng)
    op, const, imm_mask, imm_val = fab._norm_pe_cfg(pe_cfg, b,
                                                    state["pe_regs"])
    pin_vals = _pinned(fab, state, ext_in)
    sel = jax.vmap(fab._selects)(cfgs)
    want = np.asarray(kref.fabric_fused_batch_ref(
        pin_vals, sel, pin_vals, depths, op, const, imm_mask, imm_val,
        jnp.asarray(a.src), jnp.asarray(t["keep"]),
        jnp.asarray(t["pin_mask"]), jnp.asarray(t["pe_in"]),
        jnp.asarray(fab.pe_out), max_depth=CHAIN_DEPTH, word=WORD))
    c = fab.chain_consts()
    read = np.concatenate([t["pe_in"].ravel(), np.arange(n)])
    chains = fabric_chain.chain_tables(
        sel, jnp.asarray(a.src), jnp.asarray(c["root"]),
        jnp.asarray(c["res"]), jnp.asarray(c["pin_src"]),
        jnp.asarray(read, jnp.int32), CHAIN_DEPTH)
    pin_state = jnp.concatenate(
        [state["regs"], ext_in, state["mem"][:, :fab.num_mem],
         jnp.zeros((b, 1), jnp.int32)], axis=1)
    got = np.asarray(fabric_chain.chain_fixpoint(
        chains, pin_state, depths, op, const, imm_mask, imm_val,
        max_depth=CHAIN_DEPTH, word=WORD))
    np.testing.assert_array_equal(got[:, 4 * p:], want, err_msg=case)
    want_ext = np.concatenate([want, np.zeros((b, 1), np.int32)], axis=1)
    np.testing.assert_array_equal(got[:, :4 * p], want_ext[:, t["pe_in"]
                                                             .ravel()])
    hop, res = np.asarray(chains["hop"]), np.asarray(chains["res"])
    if case == "shallow":        # some chain outruns its lane's depth
        assert (hop[:, 4 * p:] > np.asarray(depths)[:, None]).any()
        assert (hop[:, 4 * p:] <= np.asarray(depths)[:, None]).any()
    if case == "pe_feedback":    # on every lane a PE input reads a PE
        assert (res[:, :4 * p] < 2 * p).any(axis=1).all()


@pytest.mark.parametrize("seed", range(4))
def test_chain_engine_matches_sweep_oracle_on_random_tables(seed):
    """Random node tables, beyond what a lowered fabric wires: PE inputs
    that are PE outputs themselves, chains into the sentinel, any lane
    depth from 0 to the bound. Every node equals the sweep oracle."""
    rng = np.random.default_rng(seed)
    b, n, f, p = 5, 150, 3, 6
    max_depth = int(rng.integers(0, 16))
    sel = rng.integers(0, f, (b, n))
    src = rng.integers(0, n + 1, (n, f))
    keep = rng.random(n) < 0.1
    pin_mask = rng.random(n) < 0.15
    out_nodes = rng.choice(n, size=2 * p, replace=False)
    keep[out_nodes], pin_mask[out_nodes] = True, False
    pe_out = out_nodes.reshape(p, 2)
    pe_in = rng.integers(0, n + 1, (p, 4))
    pe_in[0, 0], pe_in[2, 1] = pe_out[1, 0], pe_out[2, 1]
    pin_vals = np.where(pin_mask, rng.integers(0, WORD + 1, (b, n)), 0)
    depths = rng.integers(0, max_depth + 1, b)
    prog = (rng.integers(0, 14, (b, p)), rng.integers(0, WORD + 1, (b, p)),
            rng.random((b, p, 4)) < 0.2,
            rng.integers(0, WORD + 1, (b, p, 4)))
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    op, const, imm_mask, imm_val = (i32(x) for x in prog)
    want = np.asarray(kref.fabric_fused_batch_ref(
        i32(pin_vals), i32(sel), i32(pin_vals), i32(depths), op, const,
        imm_mask, imm_val, i32(src), i32(keep), i32(pin_mask), i32(pe_in),
        i32(pe_out), max_depth=max_depth, word=WORD))
    res = np.full(n + 1, 2 * p)
    res[out_nodes] = np.arange(2 * p)
    root = np.append(keep | pin_mask, True)
    read = np.concatenate([pe_in.ravel(), np.arange(n)])
    chains = fabric_chain.chain_tables(
        i32(sel), i32(src), i32(root), i32(res), i32(np.arange(n + 1)),
        i32(read), max_depth)
    pin_ext = np.concatenate([pin_vals, np.zeros((b, 1), int)], axis=1)
    got = np.asarray(fabric_chain.chain_fixpoint(
        chains, i32(pin_ext), i32(depths), op, const, imm_mask, imm_val,
        max_depth=max_depth, word=WORD))
    np.testing.assert_array_equal(got[:, 4 * p:], want)
    want_ext = np.concatenate([want, np.zeros((b, 1), np.int32)], axis=1)
    np.testing.assert_array_equal(got[:, :4 * p],
                                  want_ext[:, pe_in.ravel()])


@pytest.mark.parametrize("case", sorted(CHAIN_CASES))
def test_chain_step_batch_matches_sweep_oracle_readouts(case):
    """``step_batch`` on the chain engine, cycle after cycle: the PE
    input registers, registers, memories and IO observations equal a
    step whose fixpoint is the sweep oracle."""
    fab, cfgs, state, pe_cfg, depths, rng = _chain_case(case)
    chains = fab.chain_tables(cfgs, CHAIN_DEPTH)
    mine, theirs = dict(state), dict(state)
    for cycle in range(CHAIN_CYCLES):
        ext_in = _ext_in(fab, rng)
        mine, obs = fab.step_batch(mine, ext_in, cfgs, pe_cfg, depth=depths,
                                   max_depth=CHAIN_DEPTH, chains=chains)
        theirs, want = _oracle_step(fab, theirs, ext_in, cfgs, pe_cfg,
                                    depths)
        for k in ("pe_regs", "regs", "mem"):
            np.testing.assert_array_equal(mine[k], theirs[k],
                                          err_msg=f"{case} {k} {cycle}")
        np.testing.assert_array_equal(obs, want,
                                      err_msg=f"{case} io {cycle}")


def test_served_xla_path_never_calls_sweep_oracle(monkeypatch):
    """``run_batch`` on ``use_pallas=False`` runs the chain engine: with
    the sweep oracle made to raise, a freshly traced batch still equals
    the per-config runs."""
    def refuse(*args, **kwargs):
        raise AssertionError("the served path called the sweep oracle")

    monkeypatch.setattr(kref, "fabric_fused_batch_ref", refuse)
    fab = compile_interconnect(_ic(3, 3, 2), use_pallas=False)
    rng = np.random.default_rng(16)
    cfgs, ext, pe_cfgs = _random_workload(fab, rng, 3, 4)
    batched = np.asarray(fab.run_batch(
        jnp.asarray(cfgs), jnp.asarray(ext),
        pe_cfgs={k: jnp.asarray(v) for k, v in pe_cfgs.items()},
        shard=False))
    serial = np.stack([
        np.asarray(fab.run(
            jnp.asarray(cfgs[i]), jnp.asarray(ext[i]),
            pe_cfg={k: jnp.asarray(v[i]) for k, v in pe_cfgs.items()}))
        for i in range(3)])
    np.testing.assert_array_equal(serial, batched)
