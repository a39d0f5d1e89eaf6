"""Share of the training step's device busy time in the per-layer
recompute of the reverse scan: ops under the program's ``recompute``
named scope (``core/fused.py``, the layer's forward re-run under
``jax.vjp``), not its ``grad`` (where the pullback's ops run, renamed
``transpose(recompute)``), over the busy time of the ``jit_one_step``
runs whole inside the trace.  Moves train_tokens_per_s."""

from bench import program_trace


def read(trace, record):
    if record["kind"] != "train":
        return None
    pt = program_trace.of(trace)
    if pt is None:
        return None
    return pt.scope_share(program_trace.in_phase("recompute"))
