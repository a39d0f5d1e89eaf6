"""Share of the training step's device busy time in which a cross-chip
collective runs and nothing else does: on each chip, the time of its
collective ops inside the ``jit_one_step`` runs whole in the trace (an
asynchronous one from its ``-start`` to its ``-done``) that no other op
of that chip overlaps, over that chip's busy time in those runs,
averaged over the chips (``bench/collectives.py``).  The collectives of
a step on each chip, by kind, go to stderr and into the record.  A
one-chip trace has none and reads nothing.  Moves
train_tokens_per_s."""

from bench import collectives
from bench.harness import log


def read(trace, record):
    if record["kind"] != "train" or trace.n_devices < 2:
        return None
    chips = collectives.per_chip(trace)
    record["collectives_per_step"] = [c["per_step"] for c in chips]
    shares = [c["exposed_share"] for c in chips
              if c["exposed_share"] is not None and c["collective_s"] > 0]
    for c in chips:
        log(f"collective_exposed_share.train: device {c['device']}, per "
            f"step of {c['steps']}: " + ", ".join(
                f"{k} {v:g}" for k, v in c["per_step"].items())
            + f"; {c['collective_s']:.4f} s in collectives, "
            f"{c['exposed_s']:.4f} s exposed")
    if not shares or len(shares) != len(chips):
        return None
    return sum(shares) / len(shares)
