"""Share of the training step's device busy time in the AdaLomo rule:
ops under the program's ``update`` scope (``core/fused.py``
``apply_rule_tree``: the Pallas kernel and its jnp moments, EMA and
normalisation, in the reverse scan and for the outer weights), over the
busy time of the ``jit_one_step`` runs whole inside the trace.  Moves
train_tokens_per_s."""

from bench import program_trace


def read(trace, record):
    if record["kind"] != "train":
        return None
    pt = program_trace.of(trace)
    if pt is None:
        return None
    return pt.scope_share(program_trace.in_phase("update"))
