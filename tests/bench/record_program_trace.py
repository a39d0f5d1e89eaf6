"""Record ``tests/bench/data/program.xplane.pb`` on a TPU: one training
step of a 2-layer h2o-danube-1.8b at its published widths (B=1, S=4096,
so attention takes the flash path) through ``run()``, then a
``PagedEngine`` serving four requests, both inside one profiler trace and
one ``bench.trace_window`` span, as ``bench/run.py --trace 1`` records a
cell.  Compilation happens before the trace starts.  The file keeps what
``bench/tracefile.py`` and ``bench/program_trace.py`` read (:func:`trim`).

    python3 tests/bench/record_program_trace.py [out.xplane.pb]
"""
import dataclasses
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

OUT = ROOT / "tests" / "bench" / "data" / "program.xplane.pb"
HOST_EVENTS = ("repro.", "bench.", "DoEnqueueProgram")
DEVICE_LINES = ("XLA Ops", "XLA Modules")
# of an op's metadata stats, the scope path alone is read
METADATA_STATS = ("tf_op",)


def _keep(field, test) -> None:
    """Keep the elements of a repeated message field that pass test."""
    kept = []
    for m in field:
        if test(m):
            kept.append(type(m)())
            kept[-1].CopyFrom(m)
    del field[:]
    field.extend(kept)


def trim(path) -> None:
    """Keep the TPU planes' op and program lines and the host's spans and
    enqueue events; drop the rest (host metadata, Python call events,
    async copies), and every op metadata stat but its scope path."""
    from bench.program_trace import _xspace_class
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    _keep(space.planes, lambda p: p.name.startswith(("/device:TPU:",
                                                     "/host:CPU")))
    for plane in space.planes:
        meta = plane.event_metadata
        if plane.name.startswith("/device:TPU:"):
            _keep(plane.lines, lambda ln: ln.name in DEVICE_LINES)
            names = {k for k, v in plane.stat_metadata.items()
                     if v.name in METADATA_STATS}
            for md in meta.values():
                _keep(md.stats, lambda st: st.metadata_id in names)
        else:
            for ln in plane.lines:
                _keep(ln.events, lambda e: meta[e.metadata_id].name
                      .startswith(HOST_EVENTS))
            _keep(plane.lines, lambda ln: len(ln.events) > 0)
        used = {e.metadata_id for ln in plane.lines for e in ln.events}
        for k in [k for k in meta if k not in used]:
            del meta[k]
    Path(path).write_bytes(space.SerializeToString())


def main(out=OUT) -> int:
    from bench import harness
    try:
        harness.require_tpu(1)
    except harness.NoChip as e:
        print(f"record_program_trace: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.data.pipeline import DataConfig
    from repro.models.registry import get_arch
    from repro.run import Hook, ModelSpec, OptSpec, RunSpec, StepSpec, run
    from repro.serve.engine import PagedEngine, PagedServeConfig

    full = get_arch("h2o-danube-1.8b")
    arch = dataclasses.replace(
        full, cfg=dataclasses.replace(full.cfg, n_layers=2))
    spec = RunSpec(model=ModelSpec("h2o-danube-1.8b"),
                   data=DataConfig(vocab=arch.cfg.vocab, seq_len=4096,
                                   global_batch=1),
                   opt=OptSpec(name="adalomo", lr=1e-4,
                               schedule="constant"),
                   steps=StepSpec(total=2), log_every=0)
    engine = PagedEngine(arch, arch.init_params(jax.random.PRNGKey(1)),
                         PagedServeConfig(page_size=16, num_pages=65,
                                          max_batch=4, max_pages_per_seq=16,
                                          chunk=8, max_new_tokens=12))
    engine.warmup([16, 200])
    trace_dir = tempfile.mkdtemp(prefix="record-trace-")
    window = harness.OpenSpan()

    class StartAfterFirstStep(Hook):
        def on_step_end(self, ctx, ev):
            if ev.step == 0:
                jax.block_until_ready((ctx.params, ctx.opt_state))
                jax.profiler.start_trace(trace_dir)
                window.open("bench.trace_window")

    try:
        run(spec, arch=arch, hooks=(StartAfterFirstStep(),),
            log_fn=lambda s: None)
        prompts = [[(7 * i + j) % 1000 + 1 for j in range(n)]
                   for i, n in enumerate((40, 150, 17, 90))]
        for p in prompts[:2]:
            engine.submit(p)
        engine.step()
        for p in prompts[2:]:
            engine.submit(p)
        engine.run()
        window.close()
    finally:
        jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    shutil.copyfile(src, out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    trim(out)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
