"""A tiny danube-shaped configuration and traffic for driving whole
benchmark runs on the CPU (the program's own smoke sizes, float32)."""
from bench import harness

TINY = {"name": "tiny", "registry_id": "h2o-danube-1.8b", "smoke": True,
        "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 128, "sliding_window": 8, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "torch_dtype": "float32"}


def train_traffic(name="seq4k"):
    return dict(harness.traffic(name), seq_len=32, batch=4)


def serve_traffic():
    return dict(harness.traffic("chat"), rate=20, lead_in_s=1,
                prompt={"median": 8, "sigma": 1.0, "min": 4, "max": 24},
                output={"median": 6, "sigma": 0.5, "min": 4, "max": 12},
                engine={"page_size": 4, "chunk": 4, "max_batch": 4,
                        "max_pages_per_seq": 10, "num_pages": 41},
                check={"sample": 4}, trace_seconds=1)


def execute(cell_name, traffic, seconds=1.0, fault=None, seed=2 ** 35 + 1):
    import time

    import jax

    from bench import run
    bench = harness.benchmark()
    cell = harness.find_cell(bench, cell_name)
    return run.execute(bench, cell, seed, seconds, False, jax.devices(),
                       cfg=TINY, traffic=traffic, fault=fault,
                       t_start=time.perf_counter())
