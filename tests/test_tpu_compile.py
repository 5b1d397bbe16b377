"""Compile rehearsals of the hdp_z kernel for a TPU v5e chip, without one.

The TPU compiler is installed even where no chip is attached: lowering
and compiling for a *described* v5e topology refuses what the chip's
compiler would refuse (unaligned tiles, too much VMEM or SMEM, ops Mosaic
cannot lower), which interpret mode never shows. Nothing here runs the
kernel. Shapes are the main path's at two widths: the AP cell (K=1000,
V=7168, L=512) and PubMed width (K=1000, V=90112, L=256), each with the
kernel-prologue alias build on (``alias_in_kernel="on"``) and off (the
per-word tables training and serving sweep on by default), plus the
training delta path (kernel, then the XLA ``delta_n`` scatter) and the
training table build that makes those per-word tables once per
iteration.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and collection
by several test workers must see the same tests everywhere.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hdp import delta_n
from repro.kernels.hdp_z.hdp_z import hdp_z_pallas
from repro.kernels.hdp_z.ops import build_word_sparse_tables_masked

K, W, DOCS = 1000, 128, 64
WIDTHS = {"ap": (7168, 512), "pubmed": (90112, 256)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe it with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _args(sharding, width: str, in_kernel: bool):
    v, length = WIDTHS[width]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if in_kernel:   # apsi (K,), raw supports vals / ids (V, W)
        tables = [s((K,), jnp.float32), s((v, W), jnp.float32),
                  s((v, W), jnp.int32)]
    else:           # q_a (V,), packed tables (V, 2, W)
        tables = [s((v,), jnp.float32), s((v, 2, W), jnp.float32),
                  s((v, 2, W), jnp.int32)]
    return [s((DOCS, length), jnp.int32), s((DOCS, length), jnp.bool_),
            s((DOCS, length), jnp.int32),
            s((DOCS, length, 3), jnp.float32)] + tables


def _compile(sharding, width, in_kernel, with_delta=False):
    v, _ = WIDTHS[width]

    def step(tokens, mask, z, *rest):
        z_new, m = hdp_z_pallas(tokens, mask, z, *rest, kk=K,
                                interpret=False, in_kernel=in_kernel)
        if not with_delta:
            return z_new, m
        return z_new, m, delta_n(z, z_new, tokens, mask, K, v)

    return jax.jit(step).lower(*_args(sharding, width, in_kernel)).compile()


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("in_kernel", [True, False],
                         ids=["prologue", "epilogue"])
def test_hdp_z_compiles_for_v5e(one_chip, no_compile_cache, width,
                                in_kernel):
    compiled = _compile(one_chip, width, in_kernel)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    v, _ = WIDTHS[width]
    # the tables stay in HBM (DMA'd per token), so the program holds no
    # (K, V) temporary: a VMEM-resident delta or table would show here.
    assert mem.temp_size_in_bytes < K * v


def test_training_delta_path_compiles_for_v5e(one_chip, no_compile_cache):
    """The delta path training takes on the chip: the kernel emits
    (z, m), then ``delta_n`` scatters the (K, V) update in XLA."""
    compiled = _compile(one_chip, "ap", in_kernel=False, with_delta=True)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "scatter" in text
    v, _ = WIDTHS["ap"]
    assert compiled.memory_analysis().output_size_in_bytes >= K * v * 4


def test_training_table_build_compiles_for_v5e(one_chip, no_compile_cache):
    """The per-word table build training runs once per iteration at
    PubMed width: top-W supports, alias epilogue, block-sparse scatter
    (cap = V, every row flagged). It must lower for the chip and fit its
    16 GB with the (K, V) Phi it reads."""
    v, _ = WIDTHS["pubmed"]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def build(phi, psi, u_mask):
        return build_word_sparse_tables_masked(phi, psi, 0.1, W, u_mask, v)

    compiled = jax.jit(build).lower(
        s((K, v), jnp.float32), s((K,), jnp.float32),
        s((v,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= v * 4 + 2 * v * 2 * W * 4
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16 * 10**9


def test_compiled_kernel_refuses_unaligned_width():
    """W must fill whole 128-lane registers when compiled; the wrapper
    says so before Mosaic does."""
    s = jax.ShapeDtypeStruct
    args = [s((8, 16), jnp.int32), s((8, 16), jnp.bool_),
            s((8, 16), jnp.int32), s((8, 16, 3), jnp.float32),
            s((24,), jnp.float32), s((24, 2, 40), jnp.float32),
            s((24, 2, 40), jnp.int32)]
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.eval_shape(lambda *a: hdp_z_pallas(*a, kk=16, interpret=False),
                       *args)
