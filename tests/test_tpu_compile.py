"""Ahead-of-time compiles of the shuffle engine's kernels for a TPU v5e.

The TPU compiler is installed beside the CPU backend, and it compiles for a
chip that is described rather than attached. These tests lower the main
path's Pallas kernel at N = 2^20 for a described ``v5e:2x2`` and assert that
Mosaic accepted it (``tpu_custom_call`` in the compiled HLO): interpret
mode cannot show a tiling or lowering error, this can. One hash-exchange
stage is compiled over the four described devices, to check its
``all-to-all``, and the one-chip sort stage is compiled to check that its
payload rides in the sort (about 30 s here).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module. Keep every such compile in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import shuffle as sh
from repro.core.context import IContext
from repro.kernels.segment_reduce.ops import segment_totals

N = 1 << 20
BLOCK = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op,dtype", [("sum", jnp.int32), ("max", jnp.float32)])
def test_segment_totals_compiles_for_v5e(one_chip, op, dtype):
    ident = 0 if op == "sum" else float("-inf")

    def f(keys, valid, values):
        return segment_totals(keys, valid, values, op, jnp.asarray(ident, dtype),
                              block=BLOCK, interpret=False)

    compiled = jax.jit(f).lower(
        _arg((N,), jnp.int32, one_chip), _arg((N,), jnp.bool_, one_chip),
        _arg((N,), dtype, one_chip)).compile()
    _assert_kernel(compiled)


def test_oracle_segmented_reduce_compiles_for_v5e(one_chip):
    # the plain-JAX fallback of the segment kernel (ignis.kernels=off): a
    # 2^20-row associative_scan took minutes and GiBs to compile for v5e,
    # the chunked scan takes seconds at any length
    def f(keys, valid, values):
        return sh.segmented_reduce(keys, valid, values, jnp.add, 0)

    jax.jit(f).lower(_arg((N,), jnp.int32, one_chip), _arg((N,), jnp.bool_, one_chip),
                     _arg((N,), jnp.int32, one_chip)).compile()


def test_sort_stage_carries_payload_in_one_sort_for_v5e(topo, one_chip):
    # the p = 1 sort stage of a {key, value} block: the payload rides in one
    # multi-operand sort, and no leaf is gathered by an argsort's order
    ctx = IContext(Mesh(np.asarray(topo.devices[:1]), ("data",)), "data")
    assert ctx.executors == 1

    def f(keys, valid, values):
        return sh.sort_stage(ctx, keys, valid, {"key": keys, "value": values}, N)

    text = jax.jit(f).lower(
        _arg((N,), jnp.int32, one_chip), _arg((N,), jnp.bool_, one_chip),
        _arg((N,), jnp.int32, one_chip)).compile().as_text()
    sorts = re.findall(r" sort\(([^)]*)\)", text)  # each sort's operands
    assert len(sorts) == 1 and sorts[0].count("%") > 1, sorts
    assert " gather(" not in text


def test_psrs_sort_stage_fills_buckets_without_gather_on_four_v5e_chips(topo):
    # the four-device sort stage of one int32 key: the send buckets are
    # filled by slices of the sorted rows, so no gather has a p·C-row
    # output (one per leaf filled its buckets before)
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    ctx = IContext(mesh, "data")
    p = ctx.executors
    C = sh.capacity_for(2.0, N // p, p)
    rows = NamedSharding(mesh, P("data"))

    def f(keys, valid):
        return sh.sort_stage(ctx, keys, valid, keys, C)

    text = jax.jit(f).lower(_arg((N,), jnp.int32, rows),
                            _arg((N,), jnp.bool_, rows)).compile().as_text()
    assert p == 4 and "all-to-all" in text
    gathers = re.findall(r"= \w+\[(\d+)[^\]]*\]\S* gather\(", text)
    assert str(p * C) not in gathers, gathers


def test_hash_stage_compiles_on_four_v5e_chips(topo):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    ctx = IContext(mesh, "data")
    p = ctx.executors
    C = sh.capacity_for(2.0, N // p, p)
    rows = NamedSharding(mesh, P("data"))

    def f(keys, valid, values):
        return sh.hash_stage(ctx, keys, valid, {"key": keys, "value": values}, C)

    compiled = jax.jit(f).lower(
        _arg((N,), jnp.int32, rows), _arg((N,), jnp.bool_, rows),
        _arg((N,), jnp.int32, rows)).compile()
    text = compiled.as_text()
    assert p == 4
    assert "all-to-all" in text
    # every leaf, the bool validity included, crosses 32 bits wide: a bool
    # all-to-all of 2^25 rows per chip takes about a minute to compile
    a2a = re.findall(r"= (\w+)\[[^\]]*\]\S* all-to-all\(", text)
    assert a2a and set(a2a) <= {"s32", "u32", "f32"}, a2a
