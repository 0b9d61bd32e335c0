"""The main path's kernels compile for a TPU v5e at a deployment's size.

Nothing here runs: each case compiles for a described (not attached)
`v5e:2x2` topology, one chip of it or all four, so Mosaic, the TPU
kernel compiler, refuses here what it would refuse on the chip.  Each
case checks that the compiled program holds the Pallas kernel
(`tpu_custom_call`) and that it needs no more temporary device memory
than the bytes of the table state it updates: the kernels take the table
in its own layout, never a copy padded to the chip's (8, 128) tiles.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import atomics
from repro.core import engine
from repro.guard.scrub import _cell_digest
from repro.kernels import engine_round

N = 2 ** 24          # cells: 256 MiB of payload at k=4
P = 8192             # lanes per batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Programs compiled for a chip that is not attached cannot be read back
    # from the persistent cache; keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes of `tree` placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def _check(compiled, table_bytes: int, kernels: int = 1):
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= kernels, \
        "no Pallas kernel in the compiled program"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= table_bytes, (temp, table_bytes)


@pytest.mark.parametrize("k", [3, 4, 128])
def test_round_compiles(one_chip, k):
    """Both tiers of `make_round` (fast and slow kernel under one
    `lax.cond`) at n=2^24.  k=3, the paged KV page table's cell width, is
    padded to Mosaic's row tiling."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    data, version = s((N, k), jnp.uint32), s((N,), jnp.uint32)
    ctx = engine.LinkCtx(s((P,), jnp.int32), s((P,), jnp.uint32),
                         s((P, k), jnp.uint32), s((P,), jnp.bool_))
    ops = engine.OpBatch(s((P,), jnp.int32), s((P,), jnp.int32),
                         s((P, k), jnp.uint32), s((P, k), jnp.uint32))
    round_fn = engine_round.make_round(N, k, mode="pallas", interpret=False)
    compiled = jax.jit(round_fn).lower(data, version, ctx, ops).compile()
    _check(compiled, _nbytes((data, version)), kernels=2)


def test_apply_compiles(one_chip, monkeypatch):
    """One whole jitted `atomics.apply` round for cached_me, the program a
    caller's first batch compiles.  With no chip attached the backend is
    the CPU, so the test steers the engine to the TPU kernels itself."""
    monkeypatch.setattr(engine_round, "resolved_mode",
                        lambda mode=None: ("pallas", False))
    spec = atomics.AtomicSpec(N, 4, "cached_me", p_max=P)
    state = _on(one_chip, jax.eval_shape(lambda: atomics.init(spec)))
    ops = _on(one_chip, jax.eval_shape(lambda: atomics.make_ops(
        jnp.zeros(P, jnp.int32), jnp.zeros(P, jnp.int32), k=4)))
    ctx = _on(one_chip, jax.eval_shape(lambda: atomics.init_ctx(P, 4)))
    compiled = engine._apply.lower(spec, state, ops, ctx, "pallas",
                                   None).compile()
    _check(compiled, _nbytes(state), kernels=2)


def test_scrub_digest_compiles(one_chip):
    """The guard's digest pass over the same table."""
    spec = atomics.AtomicSpec(N, 4, "cached_me", p_max=P)
    state = _on(one_chip, jax.eval_shape(lambda: atomics.init(spec)))
    compiled = _cell_digest.lower(spec, state, "pallas", False).compile()
    _check(compiled, _nbytes(state))


def test_dist_apply_compiles(topo, one_chip, monkeypatch):
    """One donating `atomics.dist.apply` round of a cached_me table sharded
    over the four chips, n = 2^24 a chip: every shard holds both tiers'
    kernels, the exchange is all_to_alls, the round updates each shard in
    place (its state aliased to the output), and it needs no more
    temporary memory a chip than one chip's donating round of the same
    table."""
    from repro.core import distributed
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(engine_round, "resolved_mode",
                        lambda mode=None: ("pallas", False))
    mesh = make_mesh((4,), ("shard",), devices=topo.devices)
    dspec = atomics.DistSpec(atomics.AtomicSpec(4 * N, 8, "cached_me",
                                                p_max=P), "shard", 4, P // 4)
    sharded = NamedSharding(mesh, PartitionSpec("shard"))
    state = _on(sharded, jax.eval_shape(lambda: jax.tree.map(
        lambda x: jnp.stack([x] * 4), atomics.init(dspec.local_spec()))))
    ops = _on(sharded, jax.eval_shape(lambda: atomics.make_ops(
        jnp.zeros(P, jnp.int32), jnp.zeros(P, jnp.int32), k=8)))
    compiled = distributed._build_table_apply(
        mesh, dspec, "pallas", True).lower(state, None, *ops).compile()
    shard_bytes = _nbytes(state) // 4
    _check(compiled, shard_bytes, kernels=2)
    assert "all-to-all" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= shard_bytes

    lsp = dspec.local_spec()
    one = _on(one_chip, jax.eval_shape(lambda: atomics.init(lsp)))
    one_ops = _on(one_chip, jax.eval_shape(lambda: atomics.make_ops(
        jnp.zeros(P, jnp.int32), jnp.zeros(P, jnp.int32), k=8)))
    alone = engine._apply_donated.lower(lsp, one, one_ops, None, "pallas",
                                        None).compile()
    assert memory.temp_size_in_bytes <= \
        alone.memory_analysis().temp_size_in_bytes
