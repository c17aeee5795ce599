"""Request-level serving engine: continuous batching over a live ParameterDB.

The engine owns ``batch_size`` sequence *slots* backed by one paged KV
cache (:mod:`repro.serve.paged_cache`).  Requests arrive on an open-loop
clock (:mod:`repro.serve.workload`); the scheduler joins a new sequence
the moment a slot frees up and evicts it the moment it finishes — decode
never drains the batch.  Every decode step runs the full (B,) batch with
per-sequence positions; idle slots sit at pos 0 with their page tables on
the junk page, so they cost one masked lane and touch no live state.

Two prompt paths:

* **Whole-prompt prefill** (``prefill_chunk=0``, the PR-5 baseline): a
  dense B=1 prefill at admission, scattered into freshly allocated pages.
  A long prompt stalls every decoding sequence for its full prefill.
* **Chunked prefill** (``prefill_chunk=C``): each scheduler tick runs at
  most one C-token chunk of the oldest pending prompt *alongside* the
  decode batch — no drain barrier, decode latency stays bounded by one
  chunk.  A prefilling slot keeps its device page table on the junk page
  and carries recurrent state outside the batch cache until *activation*
  (``make_activate_fn``), so interleaved decode steps can't touch it.
  Chunks are end-aligned when sound (attention-only model, prompt within
  the smallest ring): the final chunk starts at ``S - C``, overlapping
  its predecessor by recomputing a few positions into the slot's private
  pages, so the prompt needs no padding, one compiled chunk shape covers
  every length, and the first token comes straight from the final
  chunk's logits.  When overlap is unsound (recurrent carry would eat
  the overlapped tokens twice, or a windowed ring wraps mid-prompt) the
  sub-chunk remainder is instead teacher-forced through the decode path
  one token per tick ("tail" phase, logits discarded until the prompt is
  exhausted) — which is also the fast path for a near-complete prefix
  hit (a fully cached prompt costs a single decode tick).

With ``prefix_cache=True`` a radix trie over prompt tokens
(:mod:`repro.serve.prefix_cache`) lets a new request adopt the physical
pages of its longest already-computed prefix: full page matches are
shared read-only under refcounts, a partially matched tail page is
adopted by copy (copy-on-write at the divergence point), and a live slot
about to overwrite a page it still shares (ring wrap) gets a
copy-on-write page first.  Finished prefills publish their full pages
back into the trie; LRU eviction over unreferenced trie leaves feeds the
allocator free list under pressure.

Sampling: ``temperature=0`` is greedy argmax; otherwise softmax sampling
with nucleus ``top_p``, keyed per request as
``fold_in(fold_in(PRNGKey(sample_seed), rid), token_index)`` — the draw
depends only on the request and token index, never on batch composition
or scheduling, so continuous and static schedules stay token-identical
even when sampling.

Parameters are never owned: each prefill and each decode step reads the
current tree from a :class:`repro.serve.live_db.LiveParamDB` (or
:class:`StaticParams`), so a trainer can publish new weights mid-serve
under the data-centric admissible-delay contract.

The classic static baseline is the same engine with ``continuous=False``:
admission only happens when every slot is free (and waits until a full
batch has arrived), which reintroduces the drain-the-batch barrier — the
difference between the two modes is purely scheduling policy, measured by
benchmarks/serve_bench.py.

Two clocks: ``"wall"`` (arrivals in seconds, ``time.perf_counter``) for
benchmarking, ``"steps"`` (arrivals in scheduler-tick indices, a virtual
clock) for deterministic tests.

Tracing: ``run`` marks its loop with ``jax.profiler`` annotations, which
cost about a microsecond each when no profiler runs and land on the
profiler's clock when one does (``jax.profiler.trace`` around ``run``).
Each loop iteration that does work is one ``repro.serve.tick`` step
(``step_num`` = the decode-step index), holding, in order,
``repro.serve.admit`` (due arrivals queued, ``_try_admit``),
``repro.serve.prefill`` (``_prefill_tick``), ``repro.serve.decode``
(copy-on-write, slot arrays to the device, the decode dispatch),
``repro.serve.readback`` (waiting for the sampled tokens) and
``repro.serve.update`` (activation, the per-slot token loop, retirement).
``repro.serve.wait`` is the sleep until the next arrival, outside any
tick; ``step_hook`` runs after its tick has closed.  Each
:class:`FinishedRequest` records when its prompt work started
(``t_start``) and how many prompt tokens went through the decode step one
a tick (``tail_tokens``), so time to first token splits into the wait
before the prompt (``t_start - arrival``) and the prompt itself
(``t_first - t_start``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from collections.abc import Mapping
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig
from ..models.transformer import (decode_step, init_chunk_carry, prefill,
                                  prefill_chunk)
from .live_db import StaticParams
from .paged_cache import (ATTN_KINDS, PageAllocator, init_paged_cache,
                          make_activate_fn, make_copy_page_fn, make_evict_fn,
                          make_join_fn)
from .prefix_cache import PrefixCache
from .workload import Request


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (model architecture comes from ModelConfig)."""
    batch_size: int = 4          # sequence slots (B_max)
    page_size: int = 8           # tokens per KV page
    cache_len: int = 128         # logical ring length for full-attn layers
    continuous: bool = True      # False = static drain-the-batch baseline
    clock: str = "wall"          # "wall" (seconds) | "steps" (ticks)
    warmup: bool = True          # compile before starting the clock
    prefill_chunk: int = 0       # chunk size; 0 = whole-prompt prefill
    prefix_cache: bool = False   # share prompt-prefix pages across requests
    prefix_seqs: int = -1        # pool headroom for retained prefixes, in
    #                              sequences' worth of pages (-1: batch_size)
    temperature: float = 0.0     # 0 = greedy argmax
    top_p: float = 1.0           # nucleus sampling mass (with temperature)
    sample_seed: int = 0         # base PRNG seed for sampling

    def __post_init__(self):
        if self.clock not in ("wall", "steps"):
            raise ValueError(f"unknown clock {self.clock!r}")
        if self.prefix_cache and self.prefill_chunk <= 0:
            # prefix adoption rides on the chunked path; default the chunk
            object.__setattr__(self, "prefill_chunk", self.page_size)
        if (self.prefix_cache or self.prefill_chunk > 0) \
                and not self.continuous:
            raise ValueError(
                "prefix_cache / prefill_chunk require continuous=True "
                "(the static baseline keeps whole-prompt prefill)")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.temperature < 0.0:
            raise ValueError("temperature must be >= 0")


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    arrival: float
    t_first: float               # clock at first token (end of prefill)
    t_done: float                # clock at last token
    tokens: tuple[int, ...]
    t_start: float = 0.0         # clock when its prompt work began
    tail_tokens: int = 0         # prompt tokens fed one a tick via decode

    @property
    def latency(self) -> float:
        return self.t_done - self.arrival

    @property
    def ttft(self) -> float:
        """Time to first token (queueing + prefill)."""
        return self.t_first - self.arrival


@dataclasses.dataclass
class ServeReport:
    mode: str                    # "continuous" | "static"
    n_requests: int
    total_tokens: int
    duration: float              # clock units (s or ticks)
    tokens_per_sec: float        # tokens / duration (per-tick for "steps")
    latency_p50: float
    latency_p99: float
    ttft_p50: float              # time-to-first-token percentiles
    ttft_p99: float
    decode_steps: int
    prefill_chunks: int          # chunked-prefill device calls issued
    prefix_hit_rate: float       # fraction of prompt tokens adopted
    utilization: float           # mean fraction of live slots per decode step
    outputs: dict[int, tuple[int, ...]]
    requests: list[FinishedRequest]  # finished records, in finishing order
    t0: float                    # time.perf_counter() when the clock read 0


class _Slot:
    __slots__ = ("req", "phase", "remaining", "tokens", "t_first",
                 "t_start", "tail_tokens", "fill_pos", "chunk_starts",
                 "carry", "rows_dev", "rows_host", "shared", "nodes")

    def __init__(self, req: Request):
        self.req = req
        self.phase = "decode"        # "prefill" | "tail" | "decode"
        self.remaining = 0
        self.tokens: list[int] = []
        self.t_first = 0.0
        self.t_start = 0.0
        self.tail_tokens = 0
        self.fill_pos = 0            # prompt positions < this are computed
        self.chunk_starts: list[int] = []  # pending prefill-chunk starts
        self.carry: Any = None       # recurrent state during chunked prefill
        self.rows_dev: dict | None = None
        self.rows_host: dict | None = None
        self.shared: dict[int, set] = {}   # {L: logical page idx shared}
        self.nodes: list = []        # trie node refs to release at retire


class ServeEngine:
    """One model, one paged cache, ``batch_size`` sequence slots."""

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig):
        if cfg.frontend == "vision":
            raise NotImplementedError(
                "serving engine is text-only for now; vision archs need "
                "per-request media plumbing through admission and decode")
        self.cfg, self.scfg = cfg, scfg
        # a raw param pytree (a Mapping) gets the frozen handle; anything
        # else exposing get() is treated as a live handle (LiveParamDB)
        self.db = (StaticParams(params)
                   if isinstance(params, Mapping) or not hasattr(params, "get")
                   else params)
        B = scfg.batch_size
        extra = 0
        if scfg.prefix_cache:
            extra = scfg.prefix_seqs if scfg.prefix_seqs >= 0 else B
        self.alloc = PageAllocator(cfg, B, scfg.cache_len, scfg.page_size,
                                   extra_seqs=extra)
        self.cache = init_paged_cache(cfg, B, scfg.cache_len, scfg.page_size,
                                      extra_seqs=extra)
        self._min_L = min(self.alloc.classes)
        if scfg.prefill_chunk > self._min_L:
            raise ValueError(
                f"prefill_chunk {scfg.prefill_chunk} exceeds the smallest "
                f"page-class ring ({self._min_L}); chunk scatter slots "
                "must stay unique within a chunk")
        # prefix adoption shares raw K/V pages — recurrent layers would
        # also need a per-prefix state snapshot, which we don't keep yet;
        # chunked prefill itself works for every layer kind via the carry
        self._all_attn = all(k in ATTN_KINDS for k in cfg.layer_kinds)
        self._can_adopt = scfg.prefix_cache and self._all_attn
        self.prefix = (PrefixCache(self.alloc, scfg.page_size)
                       if scfg.prefix_cache else None)

        self._join = jax.jit(make_join_fn(cfg, scfg.cache_len,
                                          scfg.page_size))
        self._evict = jax.jit(make_evict_fn(cfg, scfg.cache_len,
                                            scfg.page_size))
        self._activate = jax.jit(make_activate_fn(cfg, scfg.cache_len,
                                                  scfg.page_size))
        self._copy = jax.jit(make_copy_page_fn(cfg, scfg.cache_len,
                                               scfg.page_size),
                             static_argnames=("L", "set_pt"))
        self._prefill = jax.jit(lambda p, t: prefill(
            p, t, cfg, cache_len=scfg.cache_len))
        self._chunk = jax.jit(lambda p, c, t, s, r, car: prefill_chunk(
            p, c, t, s, r, car, cfg, scfg.cache_len))
        self._carry0 = init_chunk_carry(cfg)

        sampler = self._make_sampler()
        self._sample = jax.jit(sampler)

        def _step(p, c, tok, pos, rids, ctrs):
            logits, c = decode_step(p, c, tok, pos, cfg)
            return sampler(logits[:, -1], rids, ctrs), c

        self._decode = jax.jit(_step)
        self._tok = np.zeros((B, 1), np.int32)
        self._pos = np.zeros((B,), np.int32)
        self._rid = np.zeros((B,), np.int32)
        self._ctr = np.zeros((B,), np.int32)
        self.slots: list[_Slot | None] = [None] * B
        self._prefill_q: deque[int] = deque()
        self.decode_steps = 0
        self.prefill_chunks = 0
        self._live_slot_steps = 0
        self._finished: list[FinishedRequest] = []

    # -- sampling ---------------------------------------------------------

    def _make_sampler(self) -> Callable:
        """logits (B, V), rids (B,), ctrs (B,) -> next tokens (B,) int32."""
        temp, top_p = self.scfg.temperature, self.scfg.top_p
        if temp <= 0.0:
            def greedy(logits, rids, ctrs):
                return jnp.argmax(logits, -1).astype(jnp.int32)
            return greedy
        base = jax.random.PRNGKey(self.scfg.sample_seed)

        def sample(logits, rids, ctrs):
            lf = logits.astype(jnp.float32) / temp
            if top_p < 1.0:
                srt = jnp.sort(lf, axis=-1)[:, ::-1]
                pr = jax.nn.softmax(srt, axis=-1)
                cum = jnp.cumsum(pr, axis=-1)
                keep = cum - pr < top_p          # smallest nucleus >= top_p
                cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
                lf = jnp.where(lf >= cutoff[:, None], lf, -jnp.inf)

            def row(l, rid, ctr):
                key = jax.random.fold_in(jax.random.fold_in(base, rid), ctr)
                return jax.random.categorical(key, l)

            return jax.vmap(row)(lf, rids, ctrs).astype(jnp.int32)

        return sample

    def _sample_one(self, logits: jnp.ndarray, rid: int, ctr: int) -> int:
        """Sample one token from (1, V) logits (prefill outputs)."""
        return int(self._sample(logits,
                                jnp.asarray([rid], jnp.int32),
                                jnp.asarray([ctr], jnp.int32))[0])

    # -- clock ------------------------------------------------------------

    def _now(self) -> float:
        if self.scfg.clock == "wall":
            return time.perf_counter() - self._t0
        return self._vnow

    def _advance_to(self, t: float) -> None:
        """Idle fast-forward to the next arrival."""
        if self.scfg.clock == "wall":
            time.sleep(max(0.0, t - self._now()))
        else:
            self._vnow = max(self._vnow, t)

    # -- admission --------------------------------------------------------

    def _free_slot(self) -> int | None:
        for b, s in enumerate(self.slots):
            if s is None:
                return b
        return None

    def _alloc_pages(self, L: int, k: int) -> np.ndarray:
        """Allocate under prefix-cache pressure: evict LRU trie leaves
        into the free list first if the class is short."""
        if self.prefix is not None:
            self.prefix.evict_for(L, k)
        return self.alloc.alloc_pages(L, k)

    def _admit(self, req: Request, now: float) -> None:
        """Whole-prompt prefill admission (the PR-5 baseline path)."""
        params = self.db.get()
        tokens = jnp.asarray([req.prompt], jnp.int32)
        logits, dense = self._prefill(params, tokens)
        first = self._sample_one(logits, req.rid, 0)
        if req.gen_len <= 1:       # prompt-only request: done at prefill
            self._finished.append(FinishedRequest(
                req.rid, req.arrival, now, now, (first,), t_start=now))
            return
        b = self._free_slot()
        assert b is not None, "admission with no free slot"
        rows = {L: jnp.asarray(ids) for L, ids in
                self.alloc.alloc(b).items()}
        self.cache = self._join(self.cache, dense,
                                jnp.asarray(b, jnp.int32), rows)
        self._tok[b, 0] = first
        self._pos[b] = len(req.prompt)
        self._rid[b] = req.rid
        self._ctr[b] = 1
        s = _Slot(req)
        s.remaining = req.gen_len - 1
        s.tokens = [first]
        s.t_first = now
        s.t_start = now
        self.slots[b] = s

    def _admit_chunked(self, req: Request, now: float) -> None:
        """Chunked-prefill admission: assign a slot and queue it.  The
        adoption lookup and chunk plan are deferred until the slot
        reaches the head of the prefill queue (``_plan_chunks``) — by
        then any in-flight request sharing its prefix has activated and
        published its pages, so concurrent same-prefix arrivals miss at
        most once instead of once per slot."""
        b = self._free_slot()
        assert b is not None, "admission with no free slot"
        s = _Slot(req)
        s.phase = "prefill"
        s.chunk_starts = None     # not planned yet
        self.slots[b] = s
        self._prefill_q.append(b)

    def _plan_chunks(self, b: int) -> None:
        """Adopt any cached prefix, allocate the rest of the slot's
        pages, and plan the chunk schedule."""
        s = self.slots[b]
        s.t_start = self._now()
        req = s.req
        prompt, S = req.prompt, len(req.prompt)
        page = self.scfg.page_size
        C = self.scfg.prefill_chunk

        full, partial = [], None
        if self.prefix is not None and self._can_adopt and S <= self._min_L:
            full, partial = self.prefix.lookup(prompt)
        a_pg = len(full)
        adopt = a_pg * page + (partial[1] if partial else 0)
        # Chunk plan.  Preferred: end-aligned chunks, the last one starting
        # at S - C so it covers the final prompt token and its logits give
        # the first generated token directly — the final chunk may overlap
        # the one before it (or the adopted prefix), recomputing a few
        # positions into the slot's private pages.  Overlap is only sound
        # when no ring wraps during the prompt (S <= the smallest ring;
        # wrapped slots would alias recomputed positions) and no layer
        # carries recurrent state (the carry would consume the overlapped
        # tokens twice).  Otherwise: non-overlapping chunks from the
        # adoption point, with the sub-chunk remainder teacher-forced one
        # token per tick through the decode path ("tail" phase) — for a
        # near-complete prefix hit that tail IS the fast path.
        overlap = (self._all_attn and S <= self._min_L and S >= C
                   and S - adopt > 2)
        if overlap:
            # chunks must only ever write the slot's private pages: cap
            # adoption at the last page boundary <= the final chunk start
            a_pg = min(a_pg, (S - C) // page)
            full, partial = full[:a_pg], None
            base = a_pg * page
            k = -(-(S - base) // C)
            s.chunk_starts = [base + i * C for i in range(k - 1)] + [S - C]
            s.fill_pos = base
        else:
            k = (S - adopt) // C
            s.chunk_starts = [adopt + i * C for i in range(k)]
            s.fill_pos = adopt
        if full:
            self.prefix.lease(full)           # released at retire
            s.nodes += full
        if partial:
            self.prefix.lease([partial[0]])   # guard during the copy below

        rows: dict[int, np.ndarray] = {}
        for L, npp in self.alloc.classes.items():
            ids = np.empty((npp,), np.int32)
            for i, node in enumerate(full):
                ids[i] = node.pages[L]
            ids[a_pg:] = self._alloc_pages(L, npp - a_pg)
            rows[L] = ids
        if partial:
            node, _t = partial
            for L in self.alloc.classes:
                self.cache = self._copy(
                    self.cache, jnp.asarray(node.pages[L], jnp.int32),
                    jnp.asarray(rows[L][a_pg], jnp.int32), L=L,
                    set_pt=False, b=jnp.asarray(0, jnp.int32),
                    idx=jnp.asarray(0, jnp.int32))
            self.prefix.release([node], drop_pages=True)
        self.alloc.install(b, rows)

        s.rows_host = {L: self.alloc.tables[L][b] for L in rows}  # views
        s.rows_dev = {L: jnp.asarray(ids) for L, ids in rows.items()}
        s.carry = self._carry0
        s.shared = {L: set(range(a_pg)) for L in rows}

    def _can_admit(self, n_queued: int, n_left: int) -> bool:
        """Whether admission would take a request now, from a queue of
        ``n_queued`` with ``n_left`` requests not yet admitted."""
        if self.scfg.continuous:
            return n_queued > 0 and self._free_slot() is not None
        # static baseline: an empty engine AND a full batch (or the tail of
        # the workload)
        return (all(s is None for s in self.slots)
                and 0 < min(self.scfg.batch_size, n_left) <= n_queued)

    def _try_admit(self, queue: deque, now: float, n_left: int) -> None:
        if self.scfg.continuous:
            chunked = self.scfg.prefill_chunk > 0
            while queue and self._free_slot() is not None:
                req = queue.popleft()
                if chunked:
                    self._admit_chunked(req, now)
                else:
                    self._admit(req, now)
        elif self._can_admit(len(queue), n_left):
            # static baseline: admit the whole wave
            for _ in range(min(self.scfg.batch_size, n_left)):
                self._admit(queue.popleft(), now)

    # -- chunked prefill / activation -------------------------------------

    def _prefill_tick(self, params) -> tuple[int, jnp.ndarray] | None:
        """Run one chunk of the oldest pending prefill.  Returns
        ``(slot, last_logits)`` when that prefill just ran its final
        chunk (activation happens after this tick's decode)."""
        if not self._prefill_q:
            return None
        b = self._prefill_q[0]
        s = self.slots[b]
        if s.chunk_starts is None:     # head of queue: plan against the
            self._plan_chunks(b)       # freshest trie state
            if not s.chunk_starts:     # near-total hit: straight to tail
                self._prefill_q.popleft()
                return b, None
        C = self.scfg.prefill_chunk
        start = s.chunk_starts.pop(0)
        toks = jnp.asarray([s.req.prompt[start:start + C]], jnp.int32)
        logits, self.cache, s.carry = self._chunk(
            params, self.cache, toks, jnp.asarray(start, jnp.int32),
            s.rows_dev, s.carry)
        s.fill_pos = start + C
        self.prefill_chunks += 1
        if not s.chunk_starts:
            self._prefill_q.popleft()
            return b, logits
        return None

    def _activate_slot(self, b: int, last_logits, now: float) -> None:
        """Flip a prefilling slot live: install its page tables and
        recurrent carry, then either take the first token straight from
        the final chunk's logits or trickle the sub-chunk prompt
        remainder through the decode path."""
        s = self.slots[b]
        S = len(s.req.prompt)
        self.cache = self._activate(self.cache, jnp.asarray(b, jnp.int32),
                                    s.rows_dev, s.carry)
        self._rid[b] = s.req.rid
        if s.fill_pos == S:            # chunks covered the whole prompt
            first = self._sample_one(last_logits, s.req.rid, 0)
            s.phase = "decode"
            s.tokens = [first]
            s.remaining = s.req.gen_len - 1
            s.t_first = now
            self._tok[b, 0] = first
            self._pos[b] = S
            self._ctr[b] = 1
            self._insert_prefix(b)
            if s.remaining <= 0:
                self._retire(b, now)
        else:                          # remainder: teacher-forced decode
            s.phase = "tail"
            s.tail_tokens = S - s.fill_pos
            self._tok[b, 0] = s.req.prompt[s.fill_pos]
            self._pos[b] = s.fill_pos
            self._ctr[b] = 0

    def _insert_prefix(self, b: int) -> None:
        """Publish a freshly prefilled prompt's full pages to the trie."""
        s = self.slots[b]
        if (self.prefix is None or not self._can_adopt
                or len(s.req.prompt) > self._min_L):
            return
        path, new_idx = self.prefix.insert(s.req.prompt, s.rows_host)
        s.nodes += path
        for L in s.shared:
            s.shared[L].update(new_idx)

    # -- copy-on-write ----------------------------------------------------

    def _cow_tick(self) -> None:
        """Before a decode step: any live slot about to write a page it
        shares with the prefix trie (ring wrap back into an adopted or
        published page) gets a private copy, page table repointed in the
        same device call."""
        page = self.scfg.page_size
        for b, s in enumerate(self.slots):
            if s is None or s.phase == "prefill":
                continue
            p = int(self._pos[b])
            for L, shared in s.shared.items():
                if not shared:
                    continue
                pg = (p % L) // page
                if pg not in shared:
                    continue
                src = int(s.rows_host[L][pg])
                dst = int(self._alloc_pages(L, 1)[0])
                self.cache = self._copy(
                    self.cache, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32), L=L, set_pt=True,
                    b=jnp.asarray(b, jnp.int32),
                    idx=jnp.asarray(pg, jnp.int32))
                s.rows_host[L][pg] = dst   # view into alloc.tables
                self.alloc.decref(L, src)  # drop the slot's share
                shared.discard(pg)

    # -- retire -----------------------------------------------------------

    def _retire(self, b: int, now: float) -> None:
        s = self.slots[b]
        self._finished.append(FinishedRequest(
            s.req.rid, s.req.arrival, s.t_first, now, tuple(s.tokens),
            t_start=s.t_start, tail_tokens=s.tail_tokens))
        if self.prefix is not None and s.nodes:
            self.prefix.release(s.nodes)
        self.cache = self._evict(self.cache, jnp.asarray(b, jnp.int32))
        self.alloc.free_slot(b)
        self._tok[b, 0] = 0
        self._pos[b] = 0
        self._rid[b] = 0
        self._ctr[b] = 0
        self.slots[b] = None

    # -- warmup -----------------------------------------------------------

    def _warmup(self, requests: list[Request]) -> None:
        """Compile every shape the run will hit before the clock starts."""
        params = self.db.get()
        rows = {L: jnp.zeros((npp,), jnp.int32)
                for L, npp in self.alloc.classes.items()}
        if self.scfg.prefill_chunk > 0:
            C = self.scfg.prefill_chunk
            logits, cache, carry = self._chunk(
                params, self.cache, jnp.zeros((1, C), jnp.int32),
                jnp.asarray(0, jnp.int32), rows, self._carry0)
            self._activate(self.cache, jnp.asarray(0, jnp.int32), rows,
                           self._carry0)
            for L in self.alloc.classes:
                for set_pt in (False, True):
                    self._copy(self.cache, jnp.asarray(0, jnp.int32),
                               jnp.asarray(0, jnp.int32), L=L,
                               set_pt=set_pt, b=jnp.asarray(0, jnp.int32),
                               idx=jnp.asarray(0, jnp.int32))
            self._sample(jnp.zeros((1, self.cfg.vocab_size)),
                         jnp.zeros((1,), jnp.int32),
                         jnp.zeros((1,), jnp.int32))
        else:
            dense = None
            for S in sorted({len(r.prompt) for r in requests}):
                logits, dense = self._prefill(
                    params, jnp.zeros((1, S), jnp.int32))
            if dense is not None:
                self._join(self.cache, dense, jnp.asarray(0, jnp.int32),
                           rows)
            self._sample(jnp.zeros((1, self.cfg.vocab_size)),
                         jnp.zeros((1,), jnp.int32),
                         jnp.zeros((1,), jnp.int32))
        self._evict(self.cache, jnp.asarray(0, jnp.int32))
        out, _ = self._decode(params, self.cache, jnp.asarray(self._tok),
                              jnp.asarray(self._pos),
                              jnp.asarray(self._rid),
                              jnp.asarray(self._ctr))
        jax.block_until_ready(out)

    # -- main loop --------------------------------------------------------

    def _take_tokens(self, live: list[int], toks: np.ndarray,
                     now: float) -> None:
        """Advance every slot that took part in a decode step by the token
        it produced: the next prompt token for a tail slot, else a served
        token; retire finished slots."""
        for b in live:
            s = self.slots[b]
            self._live_slot_steps += 1
            tk = int(toks[b])
            self._pos[b] += 1
            if s.phase == "tail":
                p = int(self._pos[b])
                if p < len(s.req.prompt):
                    self._tok[b, 0] = s.req.prompt[p]
                else:          # tk is the first generated token
                    s.phase = "decode"
                    s.tokens = [tk]
                    s.remaining = s.req.gen_len - 1
                    s.t_first = now
                    self._tok[b, 0] = tk
                    self._ctr[b] = 1
                    self._insert_prefix(b)
                    if s.remaining <= 0:
                        self._retire(b, now)
            else:
                s.tokens.append(tk)
                self._tok[b, 0] = tk
                self._ctr[b] += 1
                s.remaining -= 1
                if s.remaining == 0:
                    self._retire(b, now)

    def run(self, requests: list[Request],
            step_hook: Callable[[int], None] | None = None) -> ServeReport:
        """Serve ``requests`` to completion; returns the run report.

        ``step_hook(decode_step_index)`` fires after every decode step —
        the deterministic stand-in for a concurrent trainer (tests publish
        new weights from it).
        """
        reqs = sorted(requests, key=lambda r: r.arrival)
        if self.scfg.warmup:
            self._warmup(reqs)
        pending = deque(reqs)
        queue: deque[Request] = deque()
        self._finished = []
        finished = self._finished
        self._t0 = time.perf_counter()
        self._vnow = 0.0

        while len(finished) < len(reqs):
            now = self._now()
            if all(s is None for s in self.slots):
                # an empty engine works this iteration only if it admits
                while pending and pending[0].arrival <= now:
                    queue.append(pending.popleft())
                if not self._can_admit(len(queue), len(pending) + len(queue)):
                    if pending:
                        with jax.profiler.TraceAnnotation("repro.serve.wait"):
                            self._advance_to(pending[0].arrival)
                    continue

            with jax.profiler.StepTraceAnnotation(
                    "repro.serve.tick", step_num=self.decode_steps):
                with jax.profiler.TraceAnnotation("repro.serve.admit"):
                    while pending and pending[0].arrival <= now:
                        queue.append(pending.popleft())
                    self._try_admit(queue, now, len(pending) + len(queue))
                live = [b for b, s in enumerate(self.slots)
                        if s is not None and s.phase != "prefill"]
                if not live and not self._prefill_q:
                    continue           # prompt-only requests, done at once

                params = self.db.get()
                with jax.profiler.TraceAnnotation("repro.serve.prefill"):
                    done_prefill = self._prefill_tick(params)
                if live:
                    with jax.profiler.TraceAnnotation("repro.serve.decode"):
                        self._cow_tick()
                        toks, self.cache = self._decode(
                            params, self.cache, jnp.asarray(self._tok),
                            jnp.asarray(self._pos), jnp.asarray(self._rid),
                            jnp.asarray(self._ctr))
                    with jax.profiler.TraceAnnotation("repro.serve.readback"):
                        toks = np.asarray(toks)
                    self.decode_steps += 1
                if self.scfg.clock == "steps":
                    self._vnow += 1.0
                now = self._now()
                with jax.profiler.TraceAnnotation("repro.serve.update"):
                    if done_prefill is not None:
                        self._activate_slot(done_prefill[0], done_prefill[1],
                                            now)
                    if live:
                        self._take_tokens(live, toks, now)
            if live and step_hook is not None:
                step_hook(self.decode_steps)

        duration = max(self._now(), 1e-9)
        lat = np.array([f.latency for f in finished])
        ttft = np.array([f.ttft for f in finished])
        total = sum(len(f.tokens) for f in finished)
        util = (self._live_slot_steps /
                (self.decode_steps * self.scfg.batch_size)
                if self.decode_steps else 0.0)
        return ServeReport(
            mode="continuous" if self.scfg.continuous else "static",
            n_requests=len(finished), total_tokens=total,
            duration=float(duration),
            tokens_per_sec=total / duration,
            latency_p50=float(np.percentile(lat, 50)),
            latency_p99=float(np.percentile(lat, 99)),
            ttft_p50=float(np.percentile(ttft, 50)),
            ttft_p99=float(np.percentile(ttft, 99)),
            decode_steps=self.decode_steps,
            prefill_chunks=self.prefill_chunks,
            prefix_hit_rate=(self.prefix.hit_rate if self.prefix else 0.0),
            utilization=util,
            outputs={f.rid: f.tokens for f in finished},
            requests=finished, t0=self._t0)
