"""The cell's device memory: the resident buffers plus what the hungriest
timed program adds, by XLA's accounting of the compiled program, and never
less than the allocator's peak."""
import types

import jax
import jax.numpy as jnp

from bench import harness


def _analysis(temp, out, alias):
    ma = types.SimpleNamespace(temp_size_in_bytes=temp,
                               output_size_in_bytes=out,
                               alias_size_in_bytes=alias)
    return types.SimpleNamespace(memory_analysis=lambda: ma)


def test_footprint_by_hand():
    mem = {"peak_bytes_in_use": 500}
    progs = {"step": _analysis(temp=900, out=300, alias=250),
             "small": _analysis(temp=10, out=5, alias=0)}
    f = harness.footprint(mem, 400, progs)
    assert f["program_extra"] == {"step": 950, "small": 15}
    assert f["bytes"] == 400 + 950
    # the allocator's peak wins where it is higher; no programs, no extra
    assert harness.footprint({"peak_bytes_in_use": 2000}, 400,
                             progs)["bytes"] == 2000
    assert harness.footprint(mem, 400, {})["bytes"] == 500


def test_donated_output_adds_only_temporaries():
    x = jnp.ones((256, 256), jnp.float32)
    nbytes = 256 * 256 * 4
    f = lambda a: jnp.tanh(a @ a) * 2.0
    donated = jax.jit(f, donate_argnums=0).lower(x).compile()
    kept = jax.jit(f).lower(x).compile()
    # an output that reuses its donated argument adds nothing; one that
    # does not adds its own bytes
    assert harness.program_extra_bytes(donated) == (
        donated.memory_analysis().temp_size_in_bytes)
    assert harness.program_extra_bytes(kept) == (
        kept.memory_analysis().temp_size_in_bytes + nbytes)
