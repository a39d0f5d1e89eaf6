"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

No arrays are ever allocated: parameters, optimizer state, batches and KV
caches are ShapeDtypeStructs; ``jit(...).lower(...).compile()`` proves the
sharding/collective story is coherent and yields ``memory_analysis()`` /
``cost_analysis()`` for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single]

Artifacts: benchmarks/artifacts/dryrun/{arch}__{shape}__{mesh}.json
(existing artifacts are skipped unless --force).
"""
import os

if __name__ == "__main__":
    # 512 host devices for the multi-pod mesh; the count locks at the first
    # jax use, so it is set before the import (and only for the CLI: the
    # roofline helpers below are imported by benchmarks)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / \
    "artifacts" / "dryrun"

# TPU v5e constants (per chip) for the roofline terms.
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # B/s
ICI_BW = 50e9              # B/s per link (≈ 45e9 measured; see DESIGN.md)

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in (SPMD, per-device) HLO.

    Also derives 'wire bytes' per op with the standard algorithm factors:
      all-gather: bytes received ≈ result; all-reduce ≈ 2×result (RS+AG);
      reduce-scatter/all-to-all/collective-permute ≈ operand.
    """
    per_kind_operand = {k: 0 for k in _COLLECTIVES}
    per_kind_wire = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", s)
        if not m:
            continue
        result_sig, opname = m.group(1), m.group(2)
        # normalize fused variants like all-gather-start
        base = None
        for k in _COLLECTIVES:
            if opname == k or opname.startswith(k + "-"):
                base = k
                break
        if base is None:
            continue
        if opname.endswith("-done"):
            continue  # counted at -start
        counts[base] += 1
        result_bytes = sum(_shape_bytes(d, s_) for d, s_ in
                           _SHAPE_RE.findall(result_sig))
        args = s[s.index("(") + 1:]
        depth, j = 1, 0
        while j < len(args) and depth:
            if args[j] == "(":
                depth += 1
            elif args[j] == ")":
                depth -= 1
            j += 1
        operand_bytes = sum(_shape_bytes(d, s_) for d, s_ in
                            _SHAPE_RE.findall(args[:j - 1]))
        per_kind_operand[base] += operand_bytes
        if base == "all-gather":
            per_kind_wire[base] += result_bytes
        elif base == "all-reduce":
            per_kind_wire[base] += 2 * result_bytes
        else:
            per_kind_wire[base] += operand_bytes
    return {
        "operand_bytes": per_kind_operand,
        "wire_bytes": per_kind_wire,
        "counts": counts,
        "total_operand_bytes": sum(per_kind_operand.values()),
        "total_wire_bytes": sum(per_kind_wire.values()),
    }


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def _mem_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes", "host_generated_code_size_in_bytes",
                 "host_argument_size_in_bytes", "host_output_size_in_bytes",
                 "host_temp_size_in_bytes", "host_alias_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    return out


def build_cell(arch_id: str, shape_name: str, mesh, *,
               optimized: bool = True, packed: bool = False):
    """Return (fn, example_args: tuple of SDS pytrees, in_shardings,
    out_shardings, donate_argnums, meta).

    Train cells build through ``repro.run.build_step_program`` — the same
    step-program constructor ``launch/train.py`` executes — so the dry-run
    lowers the identical program it would train (Run API v1 contract; no
    drift between the compiled artifact and production training).

    ``optimized=False`` reproduces the paper-faithful baseline: no
    activation-sharding policy, no gradient reduce-scatter constraint
    (EXPERIMENTS.md §Perf records both).  ``packed=True`` lowers the
    train cell on the segment-packed batch signature (tokens + labels +
    segment_ids + positions + loss_mask) instead of the padded one."""
    from repro.configs.shapes import SHAPES
    from repro.models.registry import get_arch
    from repro.sharding import rules as R
    from repro.sharding.act import ActPolicy, install

    arch = get_arch(arch_id)
    axes = R.MeshAxes(mesh)
    install(ActPolicy(mesh, axes) if optimized else None)
    sh = SHAPES[shape_name]
    params_sds = jax.eval_shape(lambda: arch.init_params(jax.random.PRNGKey(0)))
    p_specs = R.param_pspecs(params_sds, axes)
    p_shard = R.to_shardings(p_specs, mesh)
    batch_sds = arch.input_specs(shape_name, packed=packed)
    b_shard = R.to_shardings(R.batch_pspecs(batch_sds, axes), mesh)
    n_params = sum(x.size for x in jax.tree.leaves(params_sds))

    if sh.kind == "decode":
        tokens_per_step = sh.global_batch
    elif sh.kind == "prefill" and arch.family == "encdec":
        tokens_per_step = sh.global_batch * arch.cfg.n_frames  # encoder only
    else:
        tokens_per_step = sh.global_batch * sh.seq_len
    meta = {"arch": arch_id, "shape": shape_name, "kind": sh.kind,
            "n_params": int(n_params),
            "n_active_params": int(arch.cfg.active_param_count()),
            "tokens_per_step": int(tokens_per_step),
            "global_batch": sh.global_batch, "seq_len": sh.seq_len}

    if sh.kind == "train":
        from repro.data.pipeline import DataConfig
        from repro.run import (MeshSpec, ModelSpec, OptSpec, RunSpec,
                               StepSpec, build_step_program)
        rc = R.make_residual_constraint(mesh, axes)
        gc = (R.make_grad_constraint(mesh, axes, params_sds)
              if optimized else None)
        pc = (R.make_param_constraint(mesh, axes, params_sds)
              if optimized else None)
        spec = RunSpec(
            model=ModelSpec(arch=arch_id),
            data=DataConfig(vocab=arch.cfg.vocab, seq_len=sh.seq_len,
                            global_batch=sh.global_batch, packing=packed),
            opt=OptSpec(name="adalomo", schedule="constant"),
            steps=StepSpec(total=1, fused=True),
            mesh=MeshSpec(kind="multi" if mesh.devices.size > 256
                          else "single", optimized=optimized))
        program = build_step_program(spec, arch, residual_constraint=rc,
                                     grad_constraint=gc,
                                     param_constraint=pc)
        args = program.abstract_args()
        # Shard the batch the program actually takes (its abstract_args),
        # not the input_specs guess above — under packing the train batch
        # carries extra leaves (segment_ids/positions/loss_mask).
        b_shard = R.to_shardings(R.batch_pspecs(args[2], axes), mesh)
        # Provenance: the exact RunSpec this cell lowers, so the artifact
        # is replayable through launch/train.py without reconstruction.
        meta["run_spec"] = spec.to_dict()
        meta["packed"] = bool(packed)
        opt_sds = args[1]
        o_specs = R.opt_pspecs(opt_sds, params_sds, p_specs, axes)
        o_shard = R.to_shardings(o_specs, mesh)
        scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        hp_shard = jax.tree.map(lambda _: scalar, args[3])
        in_sh = (p_shard, o_shard, b_shard, hp_shard)
        out_sh = (p_shard, o_shard, scalar, scalar)
        return program.fn, args, in_sh, out_sh, (0, 1), meta

    if sh.kind == "prefill":
        if arch.family == "encdec":
            fn = arch.make_prefill_step(max_decode_len=448)
            batch_sds = {"tokens": batch_sds["tokens"],
                         "frames": batch_sds["frames"]}
            b_shard = R.to_shardings(R.batch_pspecs(batch_sds, axes), mesh)
        else:
            fn = arch.make_prefill_step()
        in_sh = (p_shard, b_shard)
        args = (params_sds, batch_sds)
        return fn, args, in_sh, None, (), meta

    # decode
    fn = arch.make_decode_step()
    cache_sds = arch.cache_specs(shape_name)
    c_specs = R.cache_pspecs(cache_sds, axes, sh.global_batch)
    c_shard = R.to_shardings(c_specs, mesh)
    in_sh = (p_shard, c_shard, b_shard)
    out_sh = None
    args = (params_sds, cache_sds, batch_sds)
    return fn, args, in_sh, out_sh, (1,), meta


def run_cell(arch_id: str, shape_name: str, mesh_kind: str, *, force=False,
             save=True, optimized: bool = True, packed: bool = False,
             artifact_dir=None) -> dict:
    from repro.launch.mesh import make_production_mesh

    adir = Path(artifact_dir) if artifact_dir else ARTIFACT_DIR
    cell = f"{arch_id}__{shape_name}__{mesh_kind}"
    if packed:
        cell += "__packed"
    out_path = adir / f"{cell}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    fn, args, in_sh, out_sh, donate, meta = build_cell(
        arch_id, shape_name, mesh, optimized=optimized, packed=packed)
    with mesh:
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=donate)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = _cost_dict(compiled)
    mem = _mem_dict(compiled)
    hlo_text = compiled.as_text()
    # Loop-aware analysis (launch/hlo_analysis.py): XLA's cost_analysis
    # counts scan bodies once; ours multiplies by trip count.
    from repro.launch.hlo_analysis import analyze
    la = analyze(hlo_text)

    res = {
        **meta,
        "mesh": mesh_kind, "n_chips": int(n_chips),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis_xla": {k: v for k, v in cost.items()
                              if isinstance(v, (int, float))},
        "memory_analysis": mem,
        "collectives": la["collectives"],
        "collectives_loop_blind": parse_collectives(hlo_text),
        "flops_per_device": la["flops"],
        "hbm_bytes_per_device": la["bytes"],
        "transcendentals_per_device": la["transcendentals"],
    }
    if save:
        adir.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(res, indent=1))
        if "run_spec" in res:
            # Sidecar: the originating RunSpec alone, loadable with
            # RunSpec.from_json for replay through launch/train.py.
            out_path.with_suffix(".runspec.json").write_text(
                json.dumps(res["run_spec"], indent=1) + "\n")
        import gzip
        with gzip.open(out_path.with_suffix(".hlo.gz"), "wt") as f:
            f.write(hlo_text)
    return res


def roofline_terms(res: dict) -> dict:
    """The three roofline terms (seconds) from a cell artifact.

    The collective term uses the bf16-equivalent wire bytes when present
    (TPU-faithful; XLA:CPU legalizes bf16 dots to fp32 before SPMD, see
    hlo_analysis.Cost.coll_wire_bf16); the raw fp32-as-lowered number is
    reported alongside as collective_s_raw."""
    compute_s = res["flops_per_device"] / PEAK_FLOPS
    memory_s = res["hbm_bytes_per_device"] / HBM_BW
    coll = res["collectives"]
    coll_raw = coll["total_wire_bytes"] / ICI_BW
    coll_s = coll.get("total_wire_bytes_bf16eq",
                      coll["total_wire_bytes"]) / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    # useful-FLOPs ratio: MODEL_FLOPS / HLO_FLOPs(global)
    n = res["n_active_params"]
    toks = res["tokens_per_step"]
    model_flops = (6 if res["kind"] == "train" else 2) * n * toks
    hlo_global = res["flops_per_device"] * res["n_chips"]
    terms.update({
        "collective_s_raw": coll_raw,
        "dominant": dom,
        "model_flops": model_flops,
        "hlo_flops_global": hlo_global,
        "useful_ratio": model_flops / hlo_global if hlo_global else 0.0,
        "roofline_fraction": (model_flops / PEAK_FLOPS / res["n_chips"])
        / bound if bound else 0.0,
    })
    return terms


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful sharding (no act-policy / "
                         "grad reduce-scatter); writes to dryrun_baseline/")
    ap.add_argument("--packed", action="store_true",
                    help="lower train cells on the segment-packed batch "
                         "layout (DataConfig.packing=True); non-train and "
                         "non-packable cells are skipped")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.shapes import SHAPES
    from repro.models.registry import ARCH_IDS, get_arch

    if args.all:
        cells = [(a, s) for a in ARCH_IDS
                 for s in get_arch(a, smoke=True).supported_cells()]
    else:
        assert args.arch, "--arch or --all required"
        shapes = ([args.shape] if args.shape else
                  get_arch(args.arch, smoke=True).supported_cells())
        cells = [(args.arch, s) for s in shapes]
    if args.packed:
        cells = [(a, s) for a, s in cells
                 if SHAPES[s].kind == "train"
                 and get_arch(a, smoke=True).supports_packing()]
        assert cells, "--packed: no packable train cells selected"
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    failures = []
    adir = (ARTIFACT_DIR.parent / "dryrun_baseline" if args.baseline
            else ARTIFACT_DIR)
    for arch_id, shape_name in cells:
        for mk in meshes:
            tag = f"{arch_id} × {shape_name} × {mk}"
            if args.packed:
                tag += " × packed"
            try:
                res = run_cell(arch_id, shape_name, mk, force=args.force,
                               optimized=not args.baseline,
                               packed=args.packed,
                               artifact_dir=adir)
                terms = roofline_terms(res)
                print(f"OK   {tag:55s} compile={res['compile_s']:7.1f}s "
                      f"dom={terms['dominant']:<13s} "
                      f"roofline={terms['roofline_fraction']:.3f}",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — report & continue
                failures.append((tag, repr(e)))
                print(f"FAIL {tag}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        sys.exit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()
