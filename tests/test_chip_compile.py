"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than present.  That refuses what interpret mode accepts:
unsupported reductions, block shapes off the (8, 128) tiling, kernels that
overflow VMEM, programs that do not fit the chip's 16 GB of HBM.  So each
case below compiles a Pallas kernel of the main path at the width it runs
at, or a whole update step (``update_1m``, and the tenant engine's
vmapped whole-lane dense step), for one chip of a ``v5e:2x2`` slice.

Every case forces ``impl='pallas'``: ``jax.default_backend()`` reads CPU
here, so ``'auto'`` would pick the XLA path and compile no kernel.  The
topology is described inside a module-scoped fixture, never at import: the
TPU library can be loaded by one process at a time, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import smscc
from repro.core import dynamic, graph_state as gs
from repro.kernels.frontier_expand import ops as frontier_ops
from repro.kernels.hash_probe import ops as hash_probe_ops
from repro.kernels.reach_blockmm import ops as blockmm_ops

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _frontier(dst, msg, nv):
    return frontier_ops.frontier_min(dst, msg, nv, impl="pallas")


def _probe(src, dst, state, base, u, v):
    return hash_probe_ops.probe(src, dst, state, base, u, v, max_probes=64,
                                impl="pallas")


# (F, NV, E): the compact repair tier of update_1m sweeps
# region_vertex_capacity = 2^20 / 8 = 2^17 slots over an edge bucket, one
# frontier (F=1) or the fused FW/BW pair (F=2); the last row is a tenant
# graph's full-table sweep (2^11 vertices, 2^13 table slots)
FRONTIER_SHAPES = [(1, 1 << 17, 4096), (1, 1 << 17, 65536),
                   (2, 1 << 17, 4096), (2, 1 << 17, 65536),
                   (1, 1 << 11, 1 << 13)]


@pytest.mark.parametrize("f,nv,e", FRONTIER_SHAPES)
def test_frontier_expand_compiles(one_chip, f, nv, e):
    compiled = _compile(
        lambda d, m: _frontier(d, m, nv),
        _sds(one_chip, (e,), jnp.int32), _sds(one_chip, (f, e), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()


# (B, C): one update_1m-sized op batch against the largest table 'auto'
# sends to the kernel, and a tenant's 256-op chunk against its 2^13 slots
PROBE_SHAPES = [(8192, 1 << 16), (256, 1 << 13)]


@pytest.mark.parametrize("b,cap", PROBE_SHAPES)
def test_hash_probe_compiles(one_chip, b, cap):
    table = [_sds(one_chip, (cap,), jnp.int32)] * 2 + \
        [_sds(one_chip, (cap,), jnp.int8)]
    keys = [_sds(one_chip, (b,), jnp.int32)] * 3
    compiled = _compile(_probe, *table, *keys)
    assert "tpu_custom_call" in compiled.as_text()


def test_kernels_compile_under_vmap(one_chip):
    """The tenant engine vmaps the whole step, so each pallas_call gets a
    batched grid: 8 tenant lanes at the tenant widths."""
    w, nv, e, b, cap = 8, 1 << 11, 1 << 13, 256, 1 << 13
    compiled = _compile(
        jax.vmap(lambda d, m: _frontier(d, m, nv)),
        _sds(one_chip, (w, e), jnp.int32), _sds(one_chip, (w, e), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()
    table = [_sds(one_chip, (w, cap), jnp.int32)] * 2 + \
        [_sds(one_chip, (w, cap), jnp.int8)]
    keys = [_sds(one_chip, (w, b), jnp.int32)] * 3
    compiled = _compile(jax.vmap(_probe), *table, *keys)
    assert "tpu_custom_call" in compiled.as_text()


def test_reach_blockmm_compiles(one_chip):
    a = _sds(one_chip, (1024, 1024), jnp.bool_)
    compiled = _compile(
        lambda x, y: blockmm_ops.bool_matmul(x, y, impl="pallas"), a, a)
    assert "tpu_custom_call" in compiled.as_text()


def test_update_1m_scan_step_fits_one_chip(one_chip):
    """The fused K=4 x B=8192 update step at update_1m with every sparse
    sweep and probe on the kernels, inside one chip's HBM."""
    shape = smscc.SHAPES["update_1m"]
    cfg = smscc.config(n_vertices=shape["n_vertices"],
                       edge_capacity=shape["edge_capacity"],
                       sparse_impl="pallas")
    state = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: gs.empty(cfg)))
    lanes = _sds(one_chip, (4, shape["batch"]), jnp.int32)
    ops = dynamic.OpBatch(kind=lanes, u=lanes, v=lanes)
    compiled = _compile(
        lambda s, o: dynamic._apply_batch_scan_impl(s, o, cfg), state, ops)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
    assert "tpu_custom_call" in compiled.as_text()


def test_tenant_dense_lane_step_fits_one_chip(one_chip):
    """The tenant engine's vmapped step at the ego-Gplus tenant widths: 16
    lanes of 1024 vertex slots and 2^18 edge slots, each repaired by one
    dense closure through the reach_blockmm kernel, inside one chip's
    HBM; the kernel's Mosaic calls keep its wrapper's name, which is how
    a trace finds them.  The probes run on XLA, as ``'auto'`` picks past
    the probe kernel's 2^16 slots."""
    cfg = smscc.config(n_vertices=1024, edge_capacity=1 << 18,
                       dense_capacity=1024, dense_matmul_impl="pallas",
                       sparse_impl="xla")
    state = jax.tree.map(
        lambda s: _sds(one_chip, (16,) + s.shape, s.dtype),
        jax.eval_shape(lambda: gs.empty(cfg)))
    lanes = _sds(one_chip, (16, 1, 64), jnp.int32)
    ops = dynamic.OpBatch(kind=lanes, u=lanes, v=lanes)
    compiled = _compile(
        jax.vmap(lambda s, o: dynamic._apply_batch_scan_impl(s, o, cfg)),
        state, ops)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert any(line.lstrip().startswith("%bool_matmul_f32")
               and "custom-call(" in line for line in text.splitlines())
