"""A serving cell: open-loop arrivals into the program's ``PagedEngine``.

Set-up builds the engine on the benchmark's weights, compiles the prefill
buckets this run's prompts fall in and the decode chunk, and runs one
request through, so nothing compiles in the window.  Then requests are
submitted when they are due, from ``lead_in_s`` before the window on, so
the window opens on a loaded engine; ``step()`` runs whenever the engine
has work.

Latency is taken from when a request was due:

* TTFT: until the ``step()`` call that returned its first token returns,
  over every request due in the window; one with no first token by the
  window's end counts at the window's end.
* TPOT: (last token time - first token time) / (tokens - 1), over the
  requests that finished in the window.

After the window, a sample of the finished requests drawn from the seed,
the longest among them, is run through the reference; the number
compared is the widest gap by which a served (greedy) token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import gen, harness, weights as W
from bench.harness import log, span
from bench.train import program_arch


def engine_config(cfg: dict, traffic: dict, seed: int):
    from repro.serve.engine import PagedServeConfig
    e = traffic["engine"]
    return PagedServeConfig(
        page_size=e["page_size"], num_pages=e["num_pages"],
        max_batch=e["max_batch"], max_pages_per_seq=e["max_pages_per_seq"],
        chunk=e["chunk"], max_new_tokens=traffic["output"]["max"],
        temperature=0.0, eos_id=-1, seed=seed & 0x7FFFFFFF)


class Recorder:
    """Wraps the engine's internal calls in spans and keeps what the
    per-layer metrics count: each prefill's real tokens and each decode
    chunk's live rows, with host times."""

    def __init__(self, engine):
        self.prefills: list[tuple[float, float, int]] = []
        self.chunks: list[tuple[float, float, list, list]] = []
        start, run_chunk = engine._start, engine._run_chunk
        admit, collect = engine._admit_all, engine._collect

        def _start(req):
            t0 = time.perf_counter()
            with span("bench.serve.prefill"):
                start(req)
            self.prefills.append((t0, time.perf_counter(), len(req.tokens)))

        def _run_chunk():
            live = ~engine._done
            n, budget = engine._n[live].tolist(), engine._budget[live].tolist()
            t0 = time.perf_counter()
            with span("bench.serve.chunk"):
                out = run_chunk()
            self.chunks.append((t0, time.perf_counter(), n, budget))
            return out

        def _admit_all():
            with span("bench.serve.admit"):
                admit()

        def _collect(toks):
            with span("bench.serve.collect"):
                collect(toks)

        engine._start, engine._run_chunk = _start, _run_chunk
        engine._admit_all, engine._collect = _admit_all, _collect


def chunk_steps(n: list, budget: list, chunk: int) -> list[list[int]]:
    """Per decode step of a chunk, the cached tokens (new one included) of
    each live row."""
    return [[ni + t + 1 for ni, bi in zip(n, budget) if t < bi]
            for t in range(chunk)]


class Window:
    """Submits due requests and steps the engine between two host
    times; records every request's times."""

    def __init__(self, engine, arrivals, lead_in: float, seconds: float):
        self.engine = engine
        self.arrivals = arrivals
        self.lead_in, self.seconds = lead_in, seconds
        self.rid = {}            # arrival index -> request id
        self.submitted, self.first, self.last = {}, {}, {}
        self.refused = 0

    def run(self, on_open=None):
        eng = self.engine
        t_origin = time.perf_counter()
        self.t_open = t_origin + self.lead_in
        self.t_close = self.t_open + self.seconds
        due = [t_origin + a.due for a in self.arrivals]
        nxt, live = 0, set()
        opened = False
        while True:
            now = time.perf_counter()
            if not opened and now >= self.t_open:
                opened = True
                if on_open is not None:
                    on_open()
            if now >= self.t_close:
                break
            while nxt < len(due) and due[nxt] <= now:
                a = self.arrivals[nxt]
                with span("bench.serve.submit"):
                    try:
                        self.rid[nxt] = eng.submit(a.prompt,
                                                   a.max_new_tokens)
                        live.add(nxt)
                    except ValueError:
                        self.refused += 1
                self.submitted[nxt] = time.perf_counter()
                nxt += 1
            if eng.scheduler.has_work():
                with span("bench.serve.step"):
                    eng.step()
                t = time.perf_counter()
                for i in list(live):
                    req = eng.requests[self.rid[i]]
                    if req.out and i not in self.first:
                        self.first[i] = t
                    if req.status == "finished":
                        self.last[i] = t
                        live.discard(i)
            else:
                wake = min(due[nxt] if nxt < len(due) else self.t_close,
                           self.t_close)
                with span("bench.serve.idle"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
        self.due = due
        return self

    def stats(self) -> dict:
        t0, t1 = self.t_open, self.t_close
        in_window = [i for i, d in enumerate(self.due) if t0 <= d < t1]
        ttft = [(min(self.first.get(i, t1), t1) - self.due[i]) * 1e3
                for i in in_window]
        done = [i for i, t in self.last.items() if t0 <= t <= t1]
        tpot = []
        for i in done:
            n = len(self.engine.requests[self.rid[i]].out)
            if n > 1:
                tpot.append((self.last[i] - self.first[i]) / (n - 1) * 1e3)
        late = [(self.submitted[i] - self.due[i]) * 1e3
                for i in self.submitted]
        return {"due_in_window": len(in_window),
                "finished_in_window": len(done),
                "ttft_ms": ttft, "tpot_ms": tpot,
                "ttft_p90_ms": harness.percentile(ttft, 90),
                "ttft_p50_ms": harness.percentile(ttft, 50),
                "tpot_p90_ms": harness.percentile(tpot, 90),
                "tpot_p50_ms": harness.percentile(tpot, 50),
                "queued_at_close": len(self.engine.scheduler.queue),
                "generator_late_ms_p50": harness.percentile(late, 50),
                "generator_late_ms_max": max(late) if late else 0.0,
                "refused": self.refused}


def finished_sample(win: Window, seed: int, k: int) -> list:
    """k finished requests drawn from the seed, the longest included."""
    eng = win.engine
    done = [eng.requests[win.rid[i]] for i in sorted(win.last)]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    longest = done[-1]
    rest = done[:-1]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[int(i)] for i in sorted(pick)]


def served_gap(ref, sample) -> tuple[float, int]:
    """Widest gap max(ref logits) - ref logit of the served token, over
    every served token of the sample; and how many tokens were compared."""
    worst, n = 0.0, 0
    for prompt, out in sample:
        logits = ref.logits(prompt + out[:-1])
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        rows = logits[pos]
        gap = rows.max(axis=1) - rows[np.arange(len(out)), out]
        worst = max(worst, float(gap.max()))
        n += len(out)
    return worst, n


def control_gap(ref, ctl, sample) -> tuple[float, int]:
    """Widest gap of the token the lower precision puts first, read with
    the reference's logits, over the same positions."""
    worst, n = 0.0, 0
    for prompt, out in sample:
        seq = prompt + out[:-1]
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        r, c = ref.logits(seq)[pos], ctl.logits(seq)[pos]
        pick = c.argmax(axis=1)
        gap = r.max(axis=1) - r[np.arange(len(out)), pick]
        worst = max(worst, float(gap.max()))
        n += len(out)
    return worst, n


def compiled_programs(engine, arrivals) -> dict:
    """The timed path's programs as the window runs them: the decode chunk
    and the prefill of each bucket warmed up."""
    import jax.numpy as jnp
    from repro.serve.engine import _bucket_len
    progs = {"decode_chunk": engine.lower_decode_chunk().compile()}
    lens = [len(a.prompt) for a in arrivals]
    b = _bucket_len(min(lens), engine.scfg.bucket_min)
    while b <= _bucket_len(max(lens), engine.scfg.bucket_min):
        batch = {"tokens": jnp.zeros((1, b), jnp.int32),
                 "length": jnp.ones((1,), jnp.int32)}
        progs[f"prefill_{b}"] = engine._prefill.lower(
            engine.params, batch).compile()
        b *= 2
    return progs


def build_engine(cfg, traffic, seed, arrivals):
    from repro.serve.engine import PagedEngine
    arch = program_arch(cfg)
    with span("bench.setup.weights"):
        params = W.init_params(cfg, seed)
    engine = PagedEngine(arch, params, engine_config(cfg, traffic, seed))
    lens = [len(a.prompt) for a in arrivals]
    with span("bench.setup.warmup"):
        engine.warmup([min(lens), max(lens)])
        # one request through admission, prefill, sampling and a chunk
        engine.generate([arrivals[0].prompt[:16]], max_new_tokens=2)
    return engine


def run_cell(cfg, traffic, seed, seconds, trace_dir, devices, counter,
             limits, fault=None) -> dict:
    import jax
    V = W.dims(cfg)["V"]
    arrivals = gen.serve_arrivals(traffic, V, seed, seconds)
    engine = build_engine(cfg, traffic, seed, arrivals)
    with span("bench.setup.programs"):
        programs = compiled_programs(engine, arrivals)
    if fault is not None:
        fault(engine)
    rec = Recorder(engine)
    mem0 = harness.memory(devices)
    setup_record = {"bytes_in_use_gib": mem0["bytes_in_use"] / harness.GIB,
                    "peak_bytes_in_use_gib":
                        mem0["peak_bytes_in_use"] / harness.GIB,
                    "compiles_in_setup": counter.count}
    lead_in = float(traffic["lead_in_s"])
    if trace_dir:
        seconds = min(float(seconds), float(traffic["trace_seconds"]))
    state = {}

    def on_open():
        state["compiles0"] = counter.count
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            state["span"] = harness.OpenSpan()
            state["span"].open("bench.trace_window")
        state["opened"] = time.perf_counter()

    win = Window(engine, arrivals, lead_in, float(seconds)).run(on_open)
    compiles = counter.count - state["compiles0"]
    if trace_dir:
        state["span"].close()
        jax.profiler.stop_trace()
    st = win.stats()
    mem = harness.memory(devices)
    foot = harness.footprint(mem, mem0["bytes_in_use"], programs)
    programs.clear()
    sample = [(list(r.prompt), list(r.out)) for r in finished_sample(
        win, seed, int(traffic["check"]["sample"]))]
    record = {"kind": "serve", "cfg": cfg, "traffic": traffic,
              "window": (state["opened"], win.t_close),
              "prefills": rec.prefills, "chunks": rec.chunks,
              "chunk": traffic["engine"]["chunk"],
              "page_size": traffic["engine"]["page_size"]}
    setup_record["lead_in_s"] = lead_in
    setup_record["window_open"] = win.t_open
    del engine, rec
    win.engine = None
    gc.collect()
    t0 = time.perf_counter()
    from bench.reference import LogitsReference
    ref = LogitsReference(cfg, seed)
    gap, n_tok = served_gap(ref, sample) if sample else (float("inf"), 0)
    lim = limits["served_logit_gap"]
    checks = {"served_logit_gap": {"value": gap, "limit": lim}}
    ok = bool(sample) and gap <= lim and st["refused"] == 0
    read = {"served_logit_gap": gap, "tokens_compared": n_tok,
            "requests_compared": len(sample),
            "reference_s": time.perf_counter() - t0}
    log(f"bench: window {st['due_in_window']} requests due, "
        f"{st['finished_in_window']} finished, queue at close "
        f"{st['queued_at_close']}, ttft p50 {st['ttft_p50_ms']:.1f} ms, "
        f"tpot p50 {st['tpot_p50_ms']:.2f} ms")
    return {"correct": ok, "attempted": st["due_in_window"],
            "failed": st["refused"], "ttft_p90_ms": st["ttft_p90_ms"],
            "tpot_p90_ms": st["tpot_p90_ms"], "memory": mem,
            "footprint": foot, "checks": checks, "readings": read,
            "record": record,
            "setup_record": setup_record, "compiles_in_window": compiles,
            "window_stats": {k: v for k, v in st.items()
                             if not isinstance(v, list)},
            "ref": ref, "sample": sample}

