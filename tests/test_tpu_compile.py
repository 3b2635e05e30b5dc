"""Main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered against shapes placed on a
described ``v5e:2x2`` topology and compiled by the TPU compiler that is
installed with JAX, which refuses what the chip would refuse (block
shapes off the (8, 128) tiling, primitives Mosaic cannot lower, more VMEM
than a kernel may use).  Interpret-mode parity lives in
``test_kernels.py`` / ``test_cluster_index.py``.

The topology is described inside a fixture, never at import, so that
only the test worker this file lands on loads the TPU library.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.adaln import adaln_modulate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.vdb_topk import (vdb_topk, vdb_topk_pernode,
                                    vdb_topk_pernode_mesh, vdb_topk_sharded,
                                    vdb_topk_sharded_mesh)

# retrieval at deployment scale: 16 queries x 4096 rows x 512 dims, k=8
Q, CAP, DIM, K = 16, 4096, 512, 8
# DiT-B/2 at 256 px: 8 slots x 256 tokens, d_model 768, 12 heads of 64
SLOTS, TOKENS, D_MODEL, HEADS = 8, 256, 768, 12


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return desc


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile cannot be read back without the chip, so
    the persistent cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape_on(topo, no_compile_cache):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("cap", [CAP, 400])   # 400: not a lane multiple
@pytest.mark.parametrize("kernel", ["vdb_topk", "vdb_topk_sharded",
                                    "vdb_topk_pernode"])
def test_retrieval_scan_compiles(shape_on, kernel, cap):
    q = shape_on((Q, DIM))
    slabs = shape_on((2, 4, cap, DIM))
    valid = shape_on((4, cap), jnp.bool_)
    if kernel == "vdb_topk":
        text = _compiled_text(
            lambda q, db, v: vdb_topk(q, db, v, K, interpret=False),
            q, shape_on((cap, DIM)), shape_on((cap,), jnp.bool_))
    elif kernel == "vdb_topk_sharded":
        text = _compiled_text(
            lambda q, s, v, n: vdb_topk_sharded(q, s, v, n, K,
                                                interpret=False),
            q, slabs, valid, shape_on((Q,), jnp.int32))
    else:
        text = _compiled_text(
            lambda q, s, v: vdb_topk_pernode(q, s, v, K, interpret=False),
            q, slabs, valid)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_dit_b2(shape_on):
    x = shape_on((SLOTS, TOKENS, HEADS, D_MODEL // HEADS))
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), x, x, x)
    assert "tpu_custom_call" in text


def test_adaln_compiles_at_dit_b2(shape_on):
    text = _compiled_text(
        lambda x, sh, sc: adaln_modulate(x, sh, sc, interpret=False),
        shape_on((SLOTS, TOKENS, D_MODEL)), shape_on((SLOTS, D_MODEL)),
        shape_on((SLOTS, D_MODEL)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ["pernode", "sharded"])
def test_mesh4_scan_compiles(topo, shape_on, mode):
    """The mesh-sharded scans on a 4-device node mesh of the described
    chips: each device holds 2 of 8 nodes and runs the Pallas kernel."""
    mesh = Mesh(np.array(topo.devices), ("nodes",),
                axis_types=(AxisType.Auto,))
    slab_s = NamedSharding(mesh, P(None, "nodes", None, None))
    rep = NamedSharding(mesh, P())
    slabs = shape_on((2, 8, CAP, DIM), sharding=slab_s)
    valid = shape_on((8, CAP), jnp.bool_,
                     sharding=NamedSharding(mesh, P("nodes", None)))
    q = shape_on((Q, DIM), sharding=rep)
    if mode == "pernode":
        compiled = jax.jit(lambda q, s, v: vdb_topk_pernode_mesh(
            q, s, v, K, mesh=mesh, use_pallas=True, interpret=False)
        ).lower(q, slabs, valid).compile()
    else:
        compiled = jax.jit(lambda q, s, v, n: vdb_topk_sharded_mesh(
            q, s, v, n, K, mesh=mesh, use_pallas=True, interpret=False)
        ).lower(q, slabs, valid,
                shape_on((Q,), jnp.int32, sharding=rep)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the slabs stay sharded: each device holds a quarter of them
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 2 * 8 * CAP * DIM * 4 / 2
