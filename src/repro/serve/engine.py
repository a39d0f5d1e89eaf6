"""Serving engines: legacy static batching + continuous batching over
paged KV.

``Engine`` is the original static-batch path (kept for the dry-run
lowering and as the benchmark baseline), fixed so the decode loop makes a
*single* host transfer per step with a device-side done mask instead of a
per-sequence ``int(tok[i])`` round-trip.

``PagedEngine`` is the production-shaped path:

  * a shared KV **page pool** on device (``serve/paging.py`` allocates,
    ``models/*.make_paged_decode_step`` reads it through the
    ``kernels/decode_attention`` paged Pallas kernel on TPU, or the jnp
    gather oracle on CPU);
  * a **scheduler** (``serve/scheduler.py``) that admits / preempts /
    retires sequences between decode chunks — requests join and leave the
    batch mid-flight;
  * **bucketed prefill**: prompts are right-padded to power-of-two length
    buckets so warmup compiles a bounded set of shapes, and prefill K/V is
    scattered into the page pool by a per-bucket jitted write;
  * one **fixed-shape jitted decode chunk**: ``chunk`` decode steps run
    on device under ``lax.scan`` with a done-mask; the host syncs once per
    chunk boundary (one ``device_get`` of tokens + state), so steady-state
    decoding never recompiles and never blocks per token.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.paging import (OutOfPages, PageAllocator,
                                build_block_tables)
from repro.serve.scheduler import RUNNING, Request, Scheduler
from repro.telemetry.serve import ServeTelemetry
from repro.telemetry.spans import span


def _sample_tokens(logits, key, temperature):
    """Greedy (temperature<=0) or temperature sampling -> int32 ids."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    max_len: int = 256
    temperature: float = 0.0      # 0 = greedy
    eos_id: int = -1              # -1 = never stop early
    seed: int = 0


class Engine:
    """Legacy static-batch engine: prefill once, decode greedily."""

    def __init__(self, arch, params, scfg: ServeConfig):
        self.arch = arch
        self.params = params
        self.scfg = scfg
        self._prefill = jax.jit(arch.make_prefill_step())
        self._decode = jax.jit(arch.make_decode_step(),
                               donate_argnums=(1,))
        eos = scfg.eos_id

        def sample_step(logits, key, tok_prev, done):
            tok = self._sample(logits, key)
            tok = jnp.where(done, tok_prev, tok)   # freeze finished rows
            if eos >= 0:
                done = done | (tok == eos)
            return tok, done

        self._sample_step = jax.jit(sample_step)

    def generate(self, prompts: list[list[int]], *,
                 extras: Optional[dict] = None) -> list[list[int]]:
        """prompts: batch of token-id lists (right-padded internally)."""
        scfg = self.scfg
        B = len(prompts)
        Lmax = max(len(p) for p in prompts)
        toks = np.zeros((B, Lmax), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p  # left-aligned; pad tail with 0
        batch = {"tokens": jnp.asarray(toks)}
        if extras:
            batch.update({k: jnp.asarray(v) for k, v in extras.items()})

        logits, cache = self._prefill(self.params, batch)
        key = jax.random.PRNGKey(scfg.seed)
        done0 = jnp.zeros((B,), bool)
        tok, done = self._sample_step(logits, key, jnp.zeros((B,), jnp.int32),
                                      done0)
        out = [[] for _ in range(B)]
        emitted_done = np.zeros(B, bool)
        for t in range(scfg.max_new_tokens):
            # ONE host sync per decode step: tokens + done mask together.
            tok_h, done_h = jax.device_get((tok, done))
            for i in range(B):
                if not emitted_done[i]:
                    out[i].append(int(tok_h[i]))
            emitted_done = done_h
            if emitted_done.all() or t == scfg.max_new_tokens - 1:
                break
            logits, cache = self._decode(self.params, cache,
                                         {"tokens": tok[:, None]})
            key = jax.random.fold_in(key, t)
            tok, done = self._sample_step(logits, key, tok, done)
        return out

    def _sample(self, logits, key):
        return _sample_tokens(logits, key, self.scfg.temperature)


# ==========================================================================
# Continuous batching over paged KV
# ==========================================================================

@dataclasses.dataclass
class PagedServeConfig:
    page_size: int = 16
    num_pages: int = 128          # shared pool size (incl. scratch page 0)
    max_batch: int = 4            # decode slots
    max_pages_per_seq: int = 16   # block-table width P
    chunk: int = 8                # decode steps between host syncs
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 = greedy
    eos_id: int = -1
    seed: int = 0
    bucket_min: int = 16          # smallest prefill bucket
    use_kernel: Optional[bool] = None   # None = Pallas kernel on TPU only
    interpret: bool = False             # Pallas interpret mode (tests)
    telemetry_path: Optional[str] = None  # serve-gauge JSONL stream
    telemetry_every: int = 1            # sample cadence in chunks
    ttl_s: float = 0.0                  # default request TTL; 0 = none


def _bucket_len(n: int, lo: int) -> int:
    b = max(lo, 1)
    while b < n:
        b *= 2
    return b


class PagedEngine:
    def __init__(self, arch, params, scfg: PagedServeConfig, *,
                 clock=time.monotonic):
        assert arch.supports_paged_serving(), arch.arch_id
        self.arch = arch
        self.params = params
        self.scfg = scfg
        # injectable monotonic clock: TTL tests advance a fake clock
        # instead of sleeping
        self.clock = clock
        B, P, ps = scfg.max_batch, scfg.max_pages_per_seq, scfg.page_size

        self.allocator = PageAllocator(scfg.num_pages, ps)
        self.scheduler = Scheduler(B, self.allocator, P)
        self._rid = itertools.count()
        self.requests: dict[int, Request] = {}
        # gauges read only host bookkeeping (allocator/scheduler state),
        # so sampling never adds a device sync to the serving hot path
        self.telemetry = (ServeTelemetry(scfg.telemetry_path,
                                         every=scfg.telemetry_every)
                          if scfg.telemetry_path else None)

        # --- device state -------------------------------------------------
        self._pages = arch.init_page_pool(scfg.num_pages, ps)
        self._key = jax.random.PRNGKey(scfg.seed)
        self._prefill_count = 0
        # host mirrors of the per-slot decode state (refreshed each chunk)
        self._tok = np.zeros(B, np.int32)
        self._n = np.zeros(B, np.int32)        # tokens in cache
        self._budget = np.zeros(B, np.int32)   # tokens still to emit
        self._done = np.ones(B, bool)          # empty slots are "done"

        # --- jitted programs ----------------------------------------------
        self._prefill = jax.jit(arch.make_prefill_kv_step())
        self._decode_chunk = jax.jit(
            self._make_chunk_fn(), donate_argnums=(1,))
        self._scatter = jax.jit(self._scatter_fn, donate_argnums=(0,))
        temp = scfg.temperature
        self._sample_jit = jax.jit(
            lambda logits, key: _sample_tokens(logits, key, temp))

    # ------------------------------------------------------------------ API
    def submit(self, prompt: list[int],
               max_new_tokens: Optional[int] = None,
               ttl_s: Optional[float] = None) -> int:
        """Queue a request; it joins the running batch at the next chunk
        boundary (mid-flight admission). Returns the request id.

        ``ttl_s`` overrides ``scfg.ttl_s`` for this request; a request
        still unfinished when its deadline passes is evicted at the next
        chunk boundary (status ``timed_out``, pages reclaimed, partial
        output kept)."""
        if max_new_tokens is None:
            max_new_tokens = self.scfg.max_new_tokens
        if ttl_s is None:
            ttl_s = self.scfg.ttl_s
        rid = next(self._rid)
        with span("repro.serve.submit", rid=rid, prompt_tokens=len(prompt)):
            req = Request(rid=rid, prompt=list(prompt),
                          max_new_tokens=max_new_tokens,
                          deadline_s=(self.clock() + ttl_s if ttl_s > 0
                                      else None))
            self.requests[rid] = req
            self.scheduler.submit(req)
        return rid

    def generate(self, prompts: list[list[int]],
                 max_new_tokens: Optional[int] = None) -> list[list[int]]:
        """Convenience: submit a batch, run to completion, return outputs
        in submission order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        self.run()
        return [self.requests[r].out for r in rids]

    def run(self) -> None:
        while self.scheduler.has_work():
            self.step()
        if self.telemetry is not None:
            self.telemetry.sample(self, force=True)

    def output(self, rid: int) -> list[int]:
        return self.requests[rid].out

    def decode_compile_count(self) -> int:
        """Number of compiled decode-chunk executables (recompile probe)."""
        return self._decode_chunk._cache_size()

    def prefill_compile_count(self) -> int:
        return self._prefill._cache_size()

    def warmup(self, prompt_lens: list[int]) -> None:
        """Compile the decode chunk + the whole pow-2 prefill-bucket ladder
        spanning prompt_lens, without touching live state."""
        lo = _bucket_len(min(prompt_lens), self.scfg.bucket_min)
        hi = _bucket_len(max(prompt_lens), self.scfg.bucket_min)
        buckets, b = [], lo
        while b <= hi:
            buckets.append(b)
            b *= 2
        for b in buckets:
            batch = {"tokens": jnp.zeros((1, b), jnp.int32),
                     "length": jnp.ones((1,), jnp.int32)}
            logits, k, v = self._prefill(self.params, batch)
            bt_row = jnp.zeros((self.scfg.max_pages_per_seq,), jnp.int32)
            self._pages = self._scatter(self._pages, k, v, bt_row,
                                        jnp.zeros((), jnp.int32))
            jax.block_until_ready(logits)
        # all slots done=True → every write is routed to the scratch page
        self._run_chunk()

    # ---------------------------------------------------------- scheduling
    def step(self) -> None:
        """One scheduling round: expire, admit, decode one chunk, retire."""
        with span("repro.serve.step"):
            with span("repro.serve.expire"):
                if self.scheduler.expire(self.clock()):
                    # deactivate the freed slots before the next chunk runs
                    for i, r in enumerate(self.scheduler.slots):
                        if r is None:
                            self._done[i] = True
            self._admit_all()
            if not self.scheduler.running():
                return
            self._ensure_ahead_all()
            t0 = time.perf_counter()
            toks = self._run_chunk()
            if self.telemetry is not None:
                self.telemetry.note_decode(time.perf_counter() - t0)
                # sample before _collect retires finished sequences, so the
                # gauge sees the pool pressure the chunk actually ran under
                self.telemetry.sample(self)
            self._collect(toks)

    def _admit_all(self) -> None:
        with span("repro.serve.admit"):
            while True:
                req = self.scheduler.admit_next()
                if req is None:
                    return
                self._start(req)

    def _start(self, req: Request) -> None:
        """(Re-)prefill req's tokens, scatter K/V into its pages, sample
        the first new token, and activate its slot."""
        scfg = self.scfg
        n = len(req.tokens)
        bucket = _bucket_len(n, scfg.bucket_min)
        # the whole prefill, first-token sync included; a request's spans
        # share its rid
        with span("repro.serve.prefill", rid=req.rid, tokens=n,
                  bucket=bucket):
            t0 = time.perf_counter()
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = req.tokens
            logits, k, v = self._prefill(
                self.params, {"tokens": jnp.asarray(toks),
                              "length": jnp.asarray([n], jnp.int32)})
            self._prefill_count += 1
            bt_row = np.zeros((scfg.max_pages_per_seq,), np.int32)
            bt_row[:len(req.pages)] = req.pages
            self._pages = self._scatter(self._pages, k, v,
                                        jnp.asarray(bt_row),
                                        jnp.asarray(n, jnp.int32))
            key = jax.random.fold_in(self._key,
                                     2 ** 20 + self._prefill_count)
            t0_tok = int(jax.device_get(self._sample_jit(logits, key))[0])
            if self.telemetry is not None:
                self.telemetry.note_prefill(time.perf_counter() - t0)
        if req.max_new_tokens > 0:
            req.out.append(t0_tok)
        req.n_cached = n
        s = req.slot
        if (scfg.eos_id >= 0 and t0_tok == scfg.eos_id) or req.budget <= 0:
            self.scheduler.finish(req)
            self._done[s] = True
            return
        self._tok[s] = t0_tok
        self._n[s] = n
        self._budget[s] = req.budget
        self._done[s] = False

    def _ensure_ahead_all(self) -> None:
        """Guarantee every running sequence has pages for the next chunk's
        writes, preempting the youngest sequences on pool exhaustion."""
        preempted = 0
        with span("repro.serve.ensure_ahead") as sp:
            for req in sorted(self.scheduler.running(),
                              key=lambda r: self.scheduler._admit_idx[r.rid]):
                if req.status != RUNNING:
                    continue   # preempted by an earlier iteration
                while True:
                    try:
                        self.scheduler.ensure_ahead(req, self.scfg.chunk)
                        break
                    except OutOfPages:
                        victim = self.scheduler.preempt_latest()
                        assert victim is not None
                        preempted += 1
                        # deactivate every slot without a running request
                        for i, r in enumerate(self.scheduler.slots):
                            if r is None:
                                self._done[i] = True
                        if victim is req:
                            break
            sp.set_metadata(preempted=preempted)

    def lower_decode_chunk(self):
        """Lower (not run) the fixed-shape decode chunk on the live state —
        what the device executes each chunk, e.g. to check for the kernel."""
        return self._decode_chunk.lower(*self._chunk_args())

    def _chunk_args(self) -> tuple:
        tables = build_block_tables(self.scheduler.page_lists(),
                                    self.scfg.max_pages_per_seq)
        return (self.params, self._pages,
                jnp.asarray(self._tok), jnp.asarray(self._n),
                jnp.asarray(self._budget), jnp.asarray(self._done),
                self._key, jnp.asarray(tables))

    def _run_chunk(self) -> np.ndarray:
        """Execute one fixed-shape jitted decode chunk; single host sync."""
        with span("repro.serve.chunk",
                  live=int(np.count_nonzero(~self._done))) as sp:
            self._pages, tok, n, budget, done, self._key, toks = (
                self._decode_chunk(*self._chunk_args()))
            # ONE transfer per chunk boundary: all post-chunk state together.
            # repro-lint: disable=R2 — the sanctioned single sync.
            tok, n, budget, done, toks = jax.device_get(
                (tok, n, budget, done, toks))
            sp.set_metadata(tokens=int(np.count_nonzero(toks >= 0)))
        # device_get returns read-only views; admissions mutate these
        self._tok, self._n = np.array(tok), np.array(n)
        self._budget, self._done = np.array(budget), np.array(done)
        return toks

    def _collect(self, toks: np.ndarray) -> None:
        """Append emitted tokens; retire finished sequences (frees pages)."""
        finished = 0
        with span("repro.serve.collect") as sp:
            for req in list(self.scheduler.running()):
                s = req.slot
                req.out.extend(int(t) for t in toks[s] if t >= 0)
                req.n_cached = int(self._n[s])
                if self._done[s]:
                    self.scheduler.finish(req)
                    finished += 1
            sp.set_metadata(finished=finished)

    # ------------------------------------------------------------- jitted
    def _make_chunk_fn(self):
        scfg = self.scfg
        decode = self.arch.make_paged_decode_step(
            use_kernel=scfg.use_kernel, interpret=scfg.interpret)
        eos, temp, T = scfg.eos_id, scfg.temperature, scfg.chunk

        def chunk(params, pages, tok, n, budget, done, key, tables):
            def one(carry, _):
                pages, tok, n, budget, done, key = carry
                emit = ~done
                logits, pages = decode(params, pages, {
                    "tokens": tok[:, None], "block_tables": tables,
                    "seq_lens": n, "emit": emit})
                key, sub = jax.random.split(key)
                nxt = _sample_tokens(logits, sub, temp)
                nxt = jnp.where(emit, nxt, tok)
                n = n + emit
                budget = budget - emit
                newly_done = emit & ((nxt == eos) if eos >= 0
                                     else jnp.zeros_like(emit))
                newly_done = newly_done | (emit & (budget <= 0))
                done = done | newly_done
                out_t = jnp.where(emit, nxt, -1)   # -1 = nothing emitted
                return (pages, nxt, n, budget, done, key), out_t

            (pages, tok, n, budget, done, key), toks = jax.lax.scan(
                one, (pages, tok, n, budget, done, key), None, length=T)
            return pages, tok, n, budget, done, key, toks.T   # toks: [B,T]

        return chunk

    @staticmethod
    def _scatter_fn(pages, k, v, bt_row, length):
        """Write prefill K/V ([L,1,S,K,dh]) into the page pool
        ([L,N,K,ps,dh]) along bt_row; positions >= length land on the
        scratch page."""
        ps = pages["k"].shape[3]
        P = bt_row.shape[0]
        S = k.shape[2]
        j = jnp.arange(S)
        valid = j < length
        pidx = jnp.where(valid, bt_row[jnp.minimum(j // ps, P - 1)], 0)
        slot = jnp.where(valid, j % ps, 0)
        # the indexed dims lead the update: [S, L, K, dh]
        return {"k": pages["k"].at[:, pidx, :, slot].set(
                    k[:, 0].transpose(1, 0, 2, 3)),
                "v": pages["v"].at[:, pidx, :, slot].set(
                    v[:, 0].transpose(1, 0, 2, 3))}
