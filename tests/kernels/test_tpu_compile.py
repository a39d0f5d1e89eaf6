"""Deviceless compiles for a TPU v5e: the main path's kernels at real widths.

Interpret mode runs a Pallas kernel as plain JAX, so it passes block
shapes and layouts that the TPU's compiler (Mosaic) refuses.  These tests
compile for a described ``v5e:2x2`` topology — nothing runs, no chip is
needed — and so catch tiling, layout and VMEM refusals at no chip time.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers all import every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.pipeline import DataConfig
from repro.kernels.adalomo_update.ops import adalomo_update
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.models.registry import get_arch
from repro.run import ModelSpec, OptSpec, RunSpec, StepSpec, build_step_program

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under the system temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a deviceless compile cannot be read back from the persistent
        # cache, so keep it out of any cache in use
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,n", [(2560, 6912), (32000, 2560)])
def test_adalomo_update_compiles_for_v5e(one_chip, m, n):
    fn = jax.jit(lambda p, g, r, c: adalomo_update(p, g, r, c, 1e-3, 2.0))
    compiled = fn.lower(_sds(one_chip, (m, n), jnp.bfloat16),
                        _sds(one_chip, (m, n), jnp.bfloat16),
                        _sds(one_chip, (m,), jnp.float32),
                        _sds(one_chip, (n,), jnp.float32)).compile()
    _assert_kernel_compiled(compiled)


@pytest.mark.parametrize("arch_id", ["h2o-danube-1.8b", "qwen3-32b"])
def test_paged_decode_attention_compiles_for_v5e(one_chip, arch_id):
    cfg = get_arch(arch_id).cfg
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, ps, P, N = 8, 16, 64, 513
    fn = jax.jit(lambda q, kp, vp, bt, sl: paged_decode_attention(
        q, kp, vp, bt, sl, use_kernel=True))
    pages = _sds(one_chip, (N, K, ps, dh), jnp.bfloat16)
    compiled = fn.lower(_sds(one_chip, (B, 1, H, dh), jnp.bfloat16),
                        pages, pages,
                        _sds(one_chip, (B, P), jnp.int32),
                        _sds(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel_compiled(compiled)


def test_fused_train_step_compiles_for_v5e(one_chip):
    """h2o-danube-1.8b at full width, depth cut to 2 layers, with the
    AdaLomo update on the Pallas kernel inside the reverse scan."""
    arch = get_arch("h2o-danube-1.8b")
    arch = dataclasses.replace(arch, cfg=dataclasses.replace(arch.cfg,
                                                             n_layers=2))
    spec = RunSpec(model=ModelSpec("h2o-danube-1.8b"),
                   data=DataConfig(vocab=0, seq_len=4096, global_batch=4),
                   opt=OptSpec(name="adalomo", kwargs={"backend": "pallas"}),
                   steps=StepSpec(total=1))
    program = build_step_program(spec, arch)
    args = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                        program.abstract_args())
    compiled = jax.jit(program.fn, donate_argnums=(0, 1)).lower(
        *args).compile()
    _assert_kernel_compiled(compiled)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)
