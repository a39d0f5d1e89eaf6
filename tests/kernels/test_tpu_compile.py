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
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.pipeline import DataConfig
from repro.kernels.adalomo_update.ops import adalomo_update
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.models.registry import get_arch
from repro.run import ModelSpec, OptSpec, RunSpec, StepSpec, build_step_program

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under the system temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a deviceless compile cannot be read back from the persistent
        # cache, so keep it out of any cache in use
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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
    # a whole tensor: the stats call and the one two-phase update call
    # (the two-call form is for shards of a tensor on a mesh)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2


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


FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq")


# B, K, G, dh, window at S=4096: h2o-danube-1.8b's widths (32 query heads
# over 8 kv heads, window 4096), and what one chip of Qwen3-32B's 2x2 step
# gets (both batch rows, 4 of the 8 kv heads with their 32 query heads)
FLASH_SHAPES = {"h2o-danube-1.8b": (1, 8, 4, 80, 4096),
                "qwen3-32b-chip-of-2x2": (2, 4, 8, 128, None)}


@pytest.mark.parametrize("name", list(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(one_chip, name):
    """Forward and backward kernels, causal, at S=4096."""
    B, K, G, dh, window = FLASH_SHAPES[name]
    S = 4096
    pos = jnp.arange(S, dtype=jnp.int32)

    def fwd_bwd(q, k, v, do):
        out, pullback = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, pos, pos, scale=dh ** -0.5, causal=True,
            window=window), q, k, v)
        return out, pullback(do)

    qs = _sds(one_chip, (B, S, K, G, dh), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, K, dh), jnp.bfloat16)
    compiled = jax.jit(fwd_bwd).lower(qs, kv, kv, qs).compile()
    text = compiled.as_text()
    for name in FLASH_KERNELS:
        assert name in text, name


def test_fused_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """h2o-danube-1.8b at full width, depth cut to 2 layers, with the
    AdaLomo update on the Pallas kernel inside the reverse scan and
    attention on the flash kernels (the dispatch asks for a TPU backend,
    which a deviceless compile does not report)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
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
    text = compiled.as_text()
    for name in FLASH_KERNELS:
        assert name in text, name
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)


def _compile_mesh_step(topo, arch_id, cfg_changes, *, seq_len=256,
                       fused=None):
    """The training step of ``arch_id`` (widths cut by ``cfg_changes``)
    on a 2x2 (data, model) mesh, built as the program's mesh path builds
    it (``fleet/elastic``), AdaLomo on its kernel: (compiled, abstract
    params, their PartitionSpecs, mesh)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.fleet.elastic import grad_constraint, program_shardings
    from repro.run.spec import MeshSpec
    from repro.sharding import rules as R
    arch = get_arch(arch_id)
    arch = dataclasses.replace(arch, cfg=dataclasses.replace(arch.cfg,
                                                             **cfg_changes))
    spec = RunSpec(model=ModelSpec(arch_id),
                   data=DataConfig(vocab=0, seq_len=seq_len, global_batch=2),
                   opt=OptSpec(name="adalomo", kwargs={"backend": "pallas"}),
                   steps=StepSpec(total=1, fused=fused),
                   mesh=MeshSpec(kind="multi", shape=(2, 2)))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    program = build_step_program(spec, arch,
                                 grad_constraint=grad_constraint(spec, arch,
                                                                 mesh))
    sh = program_shardings(program, mesh)
    args = jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=h), program.abstract_args(), sh)
    rep = NamedSharding(mesh, P())
    # the mesh in context, as the elastic runner's step sets it
    with jax.set_mesh(mesh):
        compiled = jax.jit(program.fn, in_shardings=sh,
                           out_shardings=(sh[0], sh[1], rep, rep),
                           donate_argnums=(0, 1)).lower(*args).compile()
    params = args[0]
    return compiled, params, R.param_pspecs(params, R.MeshAxes(mesh)), mesh


def _kernel_shapes(compiled):
    """The [m, n] operand shapes of the compiled AdaLomo kernel calls."""
    seen = set()
    for line in compiled.as_text().splitlines():
        if "tpu_custom_call" in line and "%adalomo_update" in line:
            seen |= {(int(m), int(n)) for m, n in
                     re.findall(r"bf16\[(\d+),(\d+)\]", line)}
    return seen


def _whole_and_local(params, specs, mesh):
    """Each sharded matrix's last two dims, whole and one device's shard."""
    from jax.sharding import PartitionSpec as P
    whole, local = set(), set()
    for leaf, pspec in zip(jax.tree.leaves(params), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))):
        shape = leaf.shape[-2:]
        if len(shape) < 2 or pspec == P():
            continue
        parts = (tuple(pspec) + (None,) * leaf.ndim)[:leaf.ndim][-2:]
        cut = [math.prod(mesh.shape[a] for a in (
            () if p is None else p if isinstance(p, tuple) else (p,)))
            for p in parts]
        local.add((shape[0] // cut[0], shape[1] // cut[1]))
        whole.add(tuple(shape))
    return whole, local


def test_mesh_step_updates_each_chips_shard_for_v5e(topo, monkeypatch):
    """The fused step of a Qwen3 block (qk-norm, GQA, untied head) on a
    2x2 (data, model) mesh: every AdaLomo kernel call takes a chip's shard
    of its matrix, never a gathered whole tensor.  Widths are cut so that
    each shard is whole blocks of the kernel (no padding hides a shape)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, params, specs, mesh = _compile_mesh_step(
        topo, "qwen3-32b", dict(n_layers=1, d_model=1024, n_heads=8,
                                n_kv_heads=2, d_ff=2048, vocab=4096))
    whole, local = _whole_and_local(params, specs, mesh)
    seen = _kernel_shapes(compiled)
    assert seen == local, (seen, local)
    assert not seen & (whole - local)


def test_mesh_step_with_experts_updates_shards_for_v5e(topo, monkeypatch):
    """A DeepSeek-MoE block on the 2x2 mesh: the experts' [E, m, n]
    weights (experts over ``model``) are updated on each chip's shard,
    the kernel mapped over the chip's own experts, like the 2-D ones."""
    from repro.models.moe import MoEConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, params, specs, mesh = _compile_mesh_step(
        topo, "deepseek-moe-16b", dict(
            n_layers=1, d_model=1024, n_heads=8, n_kv_heads=8, d_ff=1024,
            vocab=4096, moe=MoEConfig(n_routed=4, top_k=2, d_ff_expert=512,
                                      n_shared=1)))
    whole, local = _whole_and_local(params, specs, mesh)
    seen = _kernel_shapes(compiled)
    assert (512, 512) in seen           # an expert's shard: [1024/2, 512]
    assert not seen & (whole - local), seen & (whole - local)


@pytest.mark.parametrize("arch_id,cfg_changes,fused", [
    ("qwen3-32b", dict(n_layers=2, d_model=1024, n_heads=8, n_kv_heads=2,
                       d_ff=2048, vocab=4096), False),
    ("whisper-base", dict(n_enc_layers=2, n_dec_layers=2, n_frames=256),
     None),
], ids=["unfused", "encdec"])
def test_mesh_step_without_shardings_compiles_for_v5e(
        topo, monkeypatch, arch_id, cfg_changes, fused):
    """The unfused step, whose rule is given no sharding (its stacked
    leaves under ``vmap``), and the encoder-decoder's fused step compile
    on the mesh: where no sharding reaches the rule, the mesh in context
    runs the kernel whole on every device."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, params, specs, mesh = _compile_mesh_step(
        topo, arch_id, cfg_changes, fused=fused)
    assert _kernel_shapes(compiled)


def _collectives(compiled):
    """Per collective kind (all-gather, all-to-all, ...): the number of ops
    in the compiled step and the bytes of their results on one chip (an
    async start's output half)."""
    size = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1, "pred": 1}
    count, nbytes = {}, {}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s+(?:ROOT )?%\S+ = (.*?) (all-gather|all-reduce|"
                     r"all-to-all|reduce-scatter|collective-permute)"
                     r"(-start)?\(", line)
        if not m:
            continue
        shapes = re.findall(r"(bf16|f16|f32|s32|u32|s8|u8|pred)\[([\d,]*)\]",
                            m.group(1))
        if m.group(3):
            shapes = shapes[len(shapes) // 2:]
        kind = m.group(2)
        count[kind] = count.get(kind, 0) + 1
        nbytes[kind] = nbytes.get(kind, 0) + sum(
            size[d] * math.prod(int(x) for x in dims.split(",") if x)
            for d, dims in shapes)
    return count, nbytes


def test_mesh_step_runs_flash_kernels_on_each_chips_heads_for_v5e(
        topo, monkeypatch):
    """Qwen3-32B at full width (d 5120, 64 query over 8 kv heads, d_ff
    25600), 2 layers, vocabulary cut to 4096, S=4096, on the 2x2 (data,
    model) mesh: attention runs the flash kernels inside ``shard_map``,
    each chip on its own kv heads, and that layout adds no weight
    gathering against the ``jnp`` scan the step takes off a TPU."""
    cut = dict(n_layers=2, vocab=4096)
    scan = _compile_mesh_step(topo, "qwen3-32b", cut, seq_len=4096)[0]
    assert not any(name in scan.as_text() for name in FLASH_KERNELS)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    flash = _compile_mesh_step(topo, "qwen3-32b", cut, seq_len=4096)[0]
    text = flash.as_text()
    for name in FLASH_KERNELS:
        assert name in text, name
    (n_flash, b_flash), (n_scan, b_scan) = (_collectives(flash),
                                            _collectives(scan))
    # nor an all-reduce: the kernels' outputs vary only over ``model``, so
    # the transposed ``shard_map`` sums no cotangent over ``data``
    for kind in ("all-gather", "all-to-all", "all-reduce"):
        assert n_flash.get(kind, 0) <= n_scan.get(kind, 0), (kind, n_flash,
                                                             n_scan)
    assert b_flash.get("all-gather", 0) <= b_scan.get("all-gather", 0), (
        b_flash, b_scan)
