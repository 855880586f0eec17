"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see Mosaic's layout and
tiling rules, so each kernel here is lowered with ``interpret=False``
for a ``v5e:2x2`` chip that is described, not attached, and compiled by
the TPU compiler installed alongside JAX.  Nothing runs.  Sizes are the
ones the chip smoke test drives: a 2^20-leaf, fanout-128 sum tree with a
256-draw batch (two 128-row blocks, so block tiling is exercised), and
flash attention at 32 heads × 2048 tokens × head dim 128 in bf16.  The
XLA backend's sample+gather is compiled at the same tree size to check
that its descent holds no serial loop over the draws.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sumtree import make_spec
from repro.core.tree_ops import XlaTreeOps
from repro.kernels import flash_attention as FA
from repro.kernels import gather as _gather
from repro.kernels import ops as kops
from repro.kernels import sample_gather as _ksg
from repro.kernels import sumtree_sample as _ks
from repro.kernels import sumtree_update as _ku

CAPACITY = 2 ** 20
FANOUT = 128
BATCH = 256
# CartPole transition rows as the replay stores them (obs, action,
# reward, next_obs, done → feature widths)
STORAGE_WIDTHS = (4, 1, 1, 4, 1)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _levels(spec, sharding, below_root=True):
    sizes = spec.level_sizes[1:] if below_root else spec.level_sizes
    return [_sds((n // spec.fanout, spec.fanout), jnp.float32, sharding)
            for n in sizes]


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sumtree_update_compiles(one_chip):
    spec = make_spec(CAPACITY, FANOUT)
    root, *levels = _levels(spec, one_chip, below_root=False)
    col = lambda dt: _sds((BATCH,), dt, one_chip)
    fn = functools.partial(_ku.sumtree_update_levels, fanout=FANOUT,
                           interpret=False)
    _assert_kernel(fn, root, levels, col(jnp.int32), col(jnp.float32),
                   col(jnp.int32))


def test_sumtree_sample_compiles(one_chip):
    spec = make_spec(CAPACITY, FANOUT)
    fn = functools.partial(_ks.sumtree_sample_levels, capacity=CAPACITY,
                           fanout=FANOUT, interpret=False)
    _assert_kernel(fn, _levels(spec, one_chip),
                   _sds((BATCH,), jnp.float32, one_chip))


def test_sample_gather_compiles(one_chip):
    spec = make_spec(CAPACITY, FANOUT)
    n = -(-CAPACITY // _ksg.STORAGE_BLOCK) * _ksg.STORAGE_BLOCK
    mats = [_sds((n, f), jnp.float32, one_chip) for f in STORAGE_WIDTHS]
    fn = functools.partial(_ksg.sample_gather_levels, capacity=CAPACITY,
                           fanout=FANOUT, interpret=False)
    _assert_kernel(fn, _levels(spec, one_chip),
                   _sds((BATCH,), jnp.float32, one_chip), mats)


def test_gather_compiles(one_chip):
    fn = functools.partial(_gather.gather_rows, interpret=False)
    _assert_kernel(fn, _sds((CAPACITY, 4), jnp.float32, one_chip),
                   _sds((BATCH,), jnp.int32, one_chip))


@pytest.mark.parametrize("batch", [64, 256])
def test_xla_sample_gather_has_no_loop(one_chip, batch):
    """The XLA backend's descent reads each level's sibling rows with one
    row gather: the compiled program holds no serial loop over the draws."""
    spec = make_spec(CAPACITY, FANOUT)
    storage = tuple(_sds((CAPACITY, f), jnp.float32, one_chip)
                    for f in STORAGE_WIDTHS)
    fn = functools.partial(XlaTreeOps().sample_gather, spec)
    compiled = jax.jit(fn).lower(
        _sds((spec.total_size,), jnp.float32, one_chip),
        _sds((batch,), jnp.float32, one_chip), storage).compile()
    assert "while(" not in compiled.as_text()


def test_kernel_tree_fits_budget():
    """The 2^20-leaf tree the compile tests use takes the kernel path,
    not the size-based XLA fallback."""
    assert kops.kernel_path_ok(make_spec(CAPACITY, FANOUT))


@pytest.mark.parametrize("pass_", ["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, pass_):
    n, s, hd = 32, 2048, 128
    q = _sds((n, s, hd), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return FA.flash_attention_nhsd(q, k, v, "full", 0, True, True,
                                       FA.BQ, FA.BK, False)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    _assert_kernel(fwd if pass_ == "fwd" else fwd_bwd, q, q, q)
