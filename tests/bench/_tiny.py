"""Tiny danube- and Qwen3-shaped configurations and traffic for driving
whole benchmark runs on the CPU (the program's own smoke sizes,
float32)."""
from bench import harness

TINY = {"name": "tiny", "registry_id": "h2o-danube-1.8b", "smoke": True,
        "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 128, "sliding_window": 8, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "torch_dtype": "float32"}

# Qwen3's block: per-head RMSNorm on q and k, head_dim wider than d/H
TINY_QWEN3 = {"name": "tiny-qwen3", "registry_id": "qwen3-32b",
              "smoke": True, "qk_norm": True, "num_hidden_layers": 2,
              "hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "vocab_size": 128, "sliding_window": None,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
              "torch_dtype": "float32"}

CONFIGS = {"danube": TINY, "qwen3": TINY_QWEN3}


def train_traffic(name="seq4k"):
    return dict(harness.traffic(name), seq_len=32, batch=4)


def serve_traffic():
    return dict(harness.traffic("chat"), rate=20, lead_in_s=1,
                prompt={"median": 8, "sigma": 1.0, "min": 4, "max": 24},
                output={"median": 6, "sigma": 0.5, "min": 4, "max": 12},
                engine={"page_size": 4, "chunk": 4, "max_batch": 4,
                        "max_pages_per_seq": 10, "num_pages": 41},
                check={"sample": 4}, trace_seconds=1)


def execute(cell_name, traffic, seconds=1.0, fault=None, seed=2 ** 35 + 1,
            cfg=TINY, chips=None):
    """One run of the cell past the chip check, on the first ``chips``
    devices (the cell's own count unless given)."""
    import time

    import jax

    from bench import run
    bench = harness.benchmark()
    cell = dict(harness.find_cell(bench, cell_name))
    if chips is not None:
        cell["chips"] = chips
    return run.execute(bench, cell, seed, seconds, False,
                       jax.devices()[:cell["chips"]], cfg=cfg,
                       traffic=traffic, fault=fault,
                       t_start=time.perf_counter())


# Faults planted under the timed step, around whatever step the run
# drives (one device or the program's mesh path)

def _half(batch):
    n = batch["tokens"].shape[0] // 2
    return {k: v[:n] for k, v in batch.items()}


def state_unchanged(program):
    """Each step returns the state it was given."""
    import jax
    import jax.numpy as jnp
    step = program.step

    def unchanged(p, s, b, hp):
        keep = jax.tree.map(jnp.copy, (p, s))   # the step donates p, s
        return keep + tuple(step(p, s, b, hp)[2:])

    program.step = unchanged


def half_batch(program):
    """Each step sees the first half of its batch, the mean taken over
    it."""
    step = program.step
    program.step = lambda p, s, b, hp: step(p, s, _half(b), hp)
