"""Work of the algorithm each kernel implements, counted from shapes.

These are the yardstick's counts, kept apart from the program's own
(``repro.telemetry.kernels``), which count what today's implementation
moves.  A kernel is held to the least work its algorithm needs, so a
faster implementation can approach 100% of its roofline and never pass
it:

* ``adalomo_update`` reads θ and g once and writes θ once, at the
  parameter's type, and reads and writes the O(m+n) factored moments.
  Today's kernel reads g twice and θ twice, and pads MLP tensors to its
  block size; neither counts.
* ``paged_decode_attention`` reads each live sequence's own pages,
  ⌈len/page⌉ of them, plus its query and output.  Today's grid visits
  every page slot of the block table; the slots past a sequence's end do
  not count.
"""
from __future__ import annotations

import math

from bench.weights import OUTER, block_leaves, dims, leaf_shape

F32 = 4
# arithmetic per element of the AdaLomo update: g², its row and column
# sums, v̂ = r·c·k, sqrt, +ε, g/·, Σu², Σθ², θ - lr·s·u
ADALOMO_OPS_PER_ELEMENT = 15


def itemsize(cfg: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[cfg["torch_dtype"]]


def factored_shapes(cfg: dict) -> list[tuple[int, int]]:
    """Every tensor the AdaLomo kernel updates in one training step: the
    2-D leaves, each layer's slice apart."""
    out = [leaf_shape(cfg, p) for p, _ in OUTER
           if len(leaf_shape(cfg, p)) == 2]
    per_layer = [leaf_shape(cfg, p) for p, _ in block_leaves(cfg)
                 if len(leaf_shape(cfg, p)) == 2]
    return out + per_layer * dims(cfg)["L"]


def adalomo_step_work(cfg: dict) -> dict:
    """FLOPs and bytes of the AdaLomo kernel over one training step."""
    b = itemsize(cfg)
    flops = nbytes = 0
    for m, n in factored_shapes(cfg):
        flops += ADALOMO_OPS_PER_ELEMENT * m * n
        nbytes += 3 * m * n * b + 2 * (m + n) * F32
    return {"flops": flops, "bytes": nbytes}


def paged_attention_work(cfg: dict, cached: list[int], page_size: int
                         ) -> dict:
    """One decode step of the paged kernel over all layers: each live
    row's pages (``cached`` tokens, the new one included), query and
    output."""
    m = dims(cfg)
    b = itemsize(cfg)
    page_bytes = 2 * m["K"] * page_size * m["dh"] * b        # K and V
    qo = 2 * m["H"] * m["dh"] * b
    nbytes = sum(math.ceil(n / page_size) * page_bytes + qo for n in cached)
    flops = sum(4 * m["H"] * m["dh"] * n for n in cached)
    return {"flops": flops * m["L"], "bytes": nbytes * m["L"]}


def decode_step_bytes(cfg: dict, cached: list[int], page_size: int) -> int:
    """Least bytes one decode step moves: every weight once (the
    embedding only for the live rows), the live pages, queries and
    outputs, and the new keys and values."""
    m = dims(cfg)
    b = itemsize(cfg)
    from bench.flops import head_params, matmul_params
    weights = (matmul_params(cfg) + head_params(cfg)) * b
    norms = (2 * m["L"] + 1) * m["d"] * F32
    embed = len(cached) * m["d"] * b
    new_kv = len(cached) * m["L"] * 2 * m["K"] * m["dh"] * b
    return (weights + norms + embed + new_kv
            + paged_attention_work(cfg, cached, page_size)["bytes"])


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> tuple[float, str]:
    """The least time the chip can take, and which bound sets it."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
