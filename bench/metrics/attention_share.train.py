"""Share of the training step's device busy time in attention: ops whose
scope path has the ``attention`` scope (``models/layers.py``), in the
forward, its recompute (``jvp(attention)``) and the backward
(``transpose(jvp(attention))``), over the busy time of the
``jit_one_step`` runs whole inside the trace.  Moves
train_tokens_per_s."""

from bench import program_trace


def read(trace, record):
    if record["kind"] != "train":
        return None
    pt = program_trace.of(trace)
    if pt is None:
        return None
    return pt.scope_share(program_trace.under("attention"))
