"""Continuous-batching decode engine over a slotted KV-cache pool.

Scheduling model (the vLLM/Orca iteration-level loop, reduced to its
core): the engine owns ``n_slots`` decode lanes backed by one
:class:`repro.serve.cache.CachePool` allocation — or, with ``paged=True``,
one :class:`repro.serve.paged.PagedCachePool` whose KV memory is
allocated page-by-page as sequences grow. Every :meth:`Engine.step` is
one iteration of

1. **admit** — pending requests are popped into free slots; the freshly
   acquired slot ids form the step's ``reset`` mask, so slot
   re-initialization happens *inside* the compiled step (no separate
   reset executable, no host round-trip over the cache). The paged pool
   additionally gates admission on pages covering the prompt — and,
   with the **prefix cache** on, first maps the longest cached prefix of
   the prompt into the lane's block table *shared* (refcounted pages,
   no copy), so those tokens skip prefill entirely;
2. **plan** — per lane (oldest admission first): prefilling lanes are
   scheduled up to ``prefill_chunk`` prompt tokens, decode lanes exactly
   one. Under paging, each lane's block table is extended to cover its
   scheduled positions and any *shared* block the lane is about to
   write is copy-on-write remapped (private page + in-graph row copy);
   when the free list runs dry, cached-but-unreferenced prefix pages
   are reclaimed LRU-first, then the *youngest* lane is preempted
   (pages + slot freed, request re-queued at the front — deterministic
   decode regenerates its tokens identically on re-admission), and a
   lane that still cannot be covered parks for the step;
3. **decode** — one call of a compiled
   :func:`repro.train.step.make_serve_step` executable advances every
   scheduled lane. Executables are built lazily per (token width C,
   with/without logits): greedy-only traffic runs exactly the
   executables the greedy-only engine had, and the logits-returning
   variant is compiled only once a sampling request is in flight;
4. **sample** — greedy lanes take the in-executable argmax token
   (bitwise the greedy-only path); lanes with ``temperature > 0``
   re-decide host-side from the returned logits
   (:mod:`repro.serve.sampling`) under a per-token key
   ``fold_in(fold_in(PRNGKey(seed), rid), position)`` — a pure function
   of (seed, rid, absolute position), so a preempted-and-readmitted
   request regenerates the same stochastic tokens;
5. **evict** — lanes whose token completed a sequence (EOS or
   ``max_new_tokens``) release their slot (and one page reference per
   mapped page), which the next iteration's admission refills
   mid-flight. Lanes that just finished their prompt publish its full
   KV pages into the pool's prefix index first.

A request of prompt length ``S0`` occupies its lane for
``ceil(S0 / C) + n_generated`` steps (minus the prefill steps a prefix
hit skips); the first sampled token is the model output of the step
that consumed the last prompt token. Under nearest rounding the greedy
path is token-for-token identical to lock-step
:func:`repro.serve.decode.generate` (the engine parity tests assert
exact equality) — chunking, paging and prefix sharing included: a chunk
step's per-row causal masks reproduce the sequential reductions
bit-for-bit, and a paged lane's gathered KV view is index-for-index the
contiguous cache whether its pages are private, adopted or CoW copies.
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import deque
from typing import Any, Optional

import jax
import numpy as np

from repro.core.policy import PrecisionPolicy
from repro.dist.axes import activation_sharding
from repro.dist.partition import dp_axes, dp_size, serve_input_specs
from repro.serve import sampling
from repro.serve.cache import CachePool
from repro.serve.paged import PagedCachePool
from repro.train.step import make_serve_step

__all__ = ["Request", "Completion", "EngineStats", "Engine"]


def _not_full_context_attention(cfg, max_len: int) -> Optional[str]:
    """Why (cfg, max_len) is *not* an attention-only full-context stack
    — ``None`` when it is. Chunked prefill and the prefix cache share
    this gate: both assume a lane's KV at position ``p`` is a pure
    function of tokens ``[0, p]`` addressable at cache index ``p``
    (recurrent state advances strictly one token per step; ring-window
    cells are slot-contiguous and overwritten, so they can be neither
    chunk-written nor shared between lanes).
    """
    if cfg.family == "ssm" or any(
            k in ("rec", "mamba") for k in cfg.block_pattern):
        return ("an attention-only stack is required "
                "(recurrent state advances one token per step)")
    windows = [cfg.swa_window]
    if "local_attn" in cfg.block_pattern:
        windows.append(cfg.local_attn_window)
    for w in windows:
        if w is not None and w < max_len:
            return ("full-context attention is required "
                    f"(ring window {w} < max_len {max_len})")
    return None


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. ``prompt`` is a 1-D i32 token array.

    ``temperature == 0`` (default) decodes greedily; ``temperature > 0``
    samples with optional top-k / top-p filtering, deterministically per
    ``(seed, rid)`` (see :mod:`repro.serve.sampling`). The two ``*_step``
    fields are engine-internal carry: recompute preemption re-queues the
    request with its *original* admission/first-token steps, so TTFT
    accounting spans the preemption instead of restarting at it.
    """
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    admitted_step: int = -1       # engine carry across preemption
    first_token_step: int = -1    # engine carry across preemption


@dataclasses.dataclass(frozen=True)
class Completion:
    """A finished request: generated tokens + accounting."""
    rid: int
    prompt: np.ndarray
    tokens: np.ndarray            # generated continuation (EOS included)
    finish_reason: str            # "eos" | "length"
    slot: int
    admitted_step: int
    finished_step: int
    first_token_step: int = -1    # step whose output was the first sample


@dataclasses.dataclass
class EngineStats:
    """Iteration-level counters (see docs/serving.md for the math)."""
    steps: int = 0                # engine iterations = compiled-step calls
    slot_steps: int = 0           # steps × n_slots (lane capacity spent)
    active_slot_steps: int = 0    # lanes that actually computed this step
    prefill_slot_steps: int = 0   # … of which were still mid-prompt after
    tokens_generated: int = 0     # sampled continuation tokens kept
    admitted: int = 0             # requests that entered service (once each)
    finished: int = 0
    preemptions: int = 0          # lanes evicted to reclaim pages
    prefix_hits: int = 0          # admissions that matched a cached prefix
    prefix_tokens_reused: int = 0  # prefill tokens skipped via the cache
    kv_capacity_tokens: int = 0   # token capacity of the KV pool
    kv_token_steps: int = 0       # Σ over steps of live KV tokens
    kv_tokens_live: int = 0       # live KV tokens right now
    kv_pages_live: int = 0        # live pages right now (paged pool only)

    @property
    def lane_occupancy(self) -> float:
        """Fraction of lane capacity computing (active / total lanes)."""
        return self.active_slot_steps / max(self.slot_steps, 1)

    @property
    def utilization(self) -> float:
        """Fraction of KV *token* capacity holding live tokens, averaged
        over steps. This is memory utilization, not lane occupancy: a
        10-token sequence parked in a 512-token stripe counts as 10/512
        of a slot, not as a fully utilized lane (the distortion the
        paged pool exists to fix — see docs/serving.md)."""
        return self.kv_token_steps / max(self.steps *
                                         max(self.kv_capacity_tokens, 1), 1)


@dataclasses.dataclass
class _Slot:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    admitted_step: int
    seq: int                      # global admission order (preemption rank)
    fed: int = 0                  # tokens consumed so far (= next position)
    last_token: int = 0           # model output of the previous step
    first_token_step: int = -1
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    published: bool = False       # prompt prefix pushed to the index
    generated: list = dataclasses.field(default_factory=list)


class Engine:
    """Continuous-batching engine bound to (params, cfg, policy[, mesh]).

    ``n_slots`` bounds concurrency, ``max_len`` bounds per-request
    ``len(prompt) + max_new_tokens``. With a ``mesh`` the cache pool is
    sharded via ``cache_specs`` and the step inputs via
    ``serve_input_specs``; the compiled step then runs under the mesh +
    activation-sharding context exactly as the dry-run compiles it.

    ``paged=True`` backs full-context attention layers with a
    :class:`~repro.serve.paged.PagedCachePool` (``page_size`` tokens per
    page, ``n_pages`` pages — default byte-parity with the contiguous
    pool; undersubscribe it to serve more lanes per byte).
    ``prefill_chunk=C > 1`` admits prompts C tokens per iteration instead
    of one, interleaved with in-flight decodes — bounding TTFT for long
    prompts without stalling decode lanes. Chunked prefill requires an
    attention-only, full-context stack (recurrent state and ring-window
    caches advance strictly one token per step).

    ``prefix_cache=None`` (default) enables prompt-prefix sharing
    whenever it is sound — paged pool + attention-only full-context
    stack (the same gate as chunked prefill; ring-window/recurrent state
    is slot-contiguous and cannot be shared). Pass ``False`` to disable,
    ``True`` to require (raises when the config is ineligible).
    """

    def __init__(self, params, cfg, policy: PrecisionPolicy, *,
                 n_slots: int = 8, max_len: int = 128, mesh=None,
                 eos_id: Optional[int] = None, fused_decode: bool = False,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None, prefill_chunk: int = 1,
                 prefix_cache: Optional[bool] = None):
        if cfg.encdec:
            raise ValueError("Engine is decoder-only; encoder-decoder "
                             "models serve via repro.serve.decode.generate")
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        reason = _not_full_context_attention(cfg, max_len)
        if prefill_chunk > 1 and reason is not None:
            raise ValueError(f"chunked prefill: {reason}")
        self.cfg = cfg
        self.policy = policy
        self.params = params
        self.mesh = mesh
        self.eos_id = eos_id
        self.paged = bool(paged)
        self.prefill_chunk = int(prefill_chunk)
        self._fused_decode = bool(fused_decode)
        if prefix_cache is None:
            self.prefix_cache = self.paged and reason is None
        elif prefix_cache:
            if not self.paged:
                raise ValueError("prefix_cache requires paged=True "
                                 "(sharing works on page refcounts)")
            if reason is not None:
                raise ValueError(f"prefix cache: {reason}")
            self.prefix_cache = True
        else:
            self.prefix_cache = False
        if paged:
            self.pool: Any = PagedCachePool(
                params, cfg, policy, n_slots=n_slots, max_len=max_len,
                page_size=page_size, n_pages=n_pages, mesh=mesh)
            # static width of the per-step CoW copy list: each scheduled
            # lane's write range spans at most (C-1)//P + 2 blocks
            self._max_copies = n_slots * (
                (self.prefill_chunk - 1) // self.pool.page_size + 2)
        else:
            self.pool = CachePool(params, cfg, policy, n_slots=n_slots,
                                  max_len=max_len, mesh=mesh)
            self._max_copies = 0
        # compiled steps, lazily built per (token width, returns logits).
        # Greedy-only traffic compiles exactly the executables the
        # greedy-only engine had — the logits variant only exists once a
        # sampling request is actually in flight.
        self._fns: dict[tuple[int, bool], Any] = {}
        self._in_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            n_rows = self.pool.n_rows if paged else None
            self._in_shardings = {
                k: NamedSharding(mesh, s)
                for k, s in serve_input_specs(
                    n_slots, mesh, paged=paged, n_rows=n_rows,
                    chunk=prefill_chunk).items()}
            self._dp = dp_axes(mesh)
            self._mp = (mesh.shape["model"]
                        if "model" in mesh.axis_names else 1)
        self._slots: list[Optional[_Slot]] = [None] * n_slots
        self._pending: deque[Request] = deque()
        self._next_rid = 0
        self._next_seq = 0
        self.stats = EngineStats()
        self.stats.kv_capacity_tokens = (
            self.pool.capacity_tokens if paged else n_slots * max_len)

    def _fn(self, width: int, with_logits: bool):
        key = (width, with_logits)
        fn = self._fns.get(key)
        if fn is None:
            fn = jax.jit(
                make_serve_step(self.cfg, self.policy,
                                fused_decode=self._fused_decode,
                                paged=self.paged, chunk=width,
                                return_logits=with_logits),
                donate_argnums=(1,))
            self._fns[key] = fn
        return fn

    # -- request intake -----------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *,
               rid: Optional[int] = None, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, seed: int = 0) -> int:
        """Queue a request; returns its rid. Admission happens in step().

        ``temperature == 0`` decodes greedily (the bitwise-parity path);
        ``temperature > 0`` samples host-side with optional top-k/top-p,
        deterministically per ``(seed, rid)`` — resubmitting the same
        request with the same seed and rid reproduces its tokens.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.pool.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the pool max_len ({self.pool.max_len})")
        sampling.validate_sampling(temperature, top_k, top_p)
        if rid is None:
            rid = self._next_rid
        else:
            taken = {r.rid for r in self._pending}
            taken.update(s.rid for s in self._slots if s is not None)
            if rid in taken:
                raise ValueError(
                    f"rid {rid} collides with a pending or in-flight "
                    "request (completions would be ambiguous)")
        self._next_rid = max(self._next_rid, rid) + 1
        self._pending.append(Request(
            rid, prompt, int(max_new_tokens), temperature=float(temperature),
            top_k=int(top_k), top_p=float(top_p), seed=int(seed)))
        return rid

    def has_work(self) -> bool:
        return bool(self._pending) or any(s is not None for s in self._slots)

    # -- scheduling helpers -------------------------------------------------
    def _admit(self, reset: np.ndarray) -> None:
        """Pop pending requests into free slots (FIFO, no reordering).

        The paged pool additionally gates on pages covering the request's
        prompt plus one decode page — counting reclaimable cached-prefix
        pages as available, and *not* counting the blocks a prefix-cache
        match already covers (those pages are adopted shared, and are
        excluded from reclaim so admission cannot evict its own match).
        A request whose prompt prefix is cached starts with ``fed`` past
        the matched blocks: the skipped positions never enter prefill.
        """
        while self._pending and self.pool.n_free:
            req = self._pending[0]
            matched: list[int] = []
            if self.paged:
                if self.prefix_cache:
                    matched = self.pool.match_prefix(req.prompt)
                need = self.pool.blocks_for(min(req.prompt.size + 1,
                                                self.pool.max_len))
                avail = (self.pool.n_free_pages +
                         self.pool.n_reclaimable(exclude=matched))
                if avail < need - len(matched):
                    break
            self._pending.popleft()
            slot = self.pool.acquire()
            fed0 = 0
            if matched:
                self.pool.adopt_prefix(slot, matched)
                # never skip the whole prompt: the last prompt token is
                # re-fed to produce the first-token logits (its write
                # into the shared final block copy-on-write remaps it)
                fed0 = min(len(matched) * self.pool.page_size,
                           req.prompt.size - 1)
                self.stats.prefix_hits += 1
                self.stats.prefix_tokens_reused += fed0
            admitted = (req.admitted_step if req.admitted_step >= 0
                        else self.stats.steps)
            self._slots[slot] = _Slot(
                req.rid, req.prompt, req.max_new_tokens, admitted,
                self._next_seq, fed=fed0,
                first_token_step=req.first_token_step,
                temperature=req.temperature, top_k=req.top_k,
                top_p=req.top_p, seed=req.seed)
            self._next_seq += 1
            reset[slot] = True
            if req.admitted_step < 0:   # first admission, not a re-entry
                self.stats.admitted += 1

    def _preempt(self, victim: int, reset: np.ndarray) -> None:
        """Evict a lane to reclaim its pages; its request re-queues at the
        front and — decode and sampling keys both being deterministic —
        regenerates the same tokens on re-admission (vLLM's recompute
        preemption). The original ``admitted_step``/``first_token_step``
        ride along on the re-queued request: TTFT and admission counts
        span the preemption rather than restarting at re-admission."""
        s = self._slots[victim]
        self._slots[victim] = None
        self.pool.release(victim)
        reset[victim] = False   # nothing left to reset; slot is free again
        self._pending.appendleft(Request(
            s.rid, s.prompt, s.max_new_tokens, temperature=s.temperature,
            top_k=s.top_k, top_p=s.top_p, seed=s.seed,
            admitted_step=s.admitted_step,
            first_token_step=s.first_token_step))
        self.stats.preemptions += 1
        # regenerated tokens are recounted on re-admission; admitted is
        # deliberately NOT decremented (it counts requests, not events)
        self.stats.tokens_generated -= len(s.generated)

    def _plan(self, reset: np.ndarray, page_reset: Optional[np.ndarray],
              copies: list) -> np.ndarray:
        """Tokens to feed per lane this step ((N,) i32, 0 = parked).

        Oldest admission first, so page pressure falls on the youngest
        lanes: a lane that cannot get its blocks preempts strictly
        younger lanes (never an already-planned one), and parks if it is
        the youngest itself. Under paging each scheduled lane's write
        range is readied by ``prepare_write`` — fresh pages join the
        step's ``page_reset`` mask, copy-on-write remaps of shared
        blocks append (dst, src) rows to ``copies``.
        """
        n = self.pool.n_slots
        feeds = np.zeros((n,), np.int32)
        order = sorted((i for i in range(n) if self._slots[i] is not None),
                       key=lambda i: self._slots[i].seq)
        for i in order:
            s = self._slots[i]
            if s is None:        # preempted by an older lane this step
                continue
            remaining = s.prompt.size - s.fed
            c = min(self.prefill_chunk, remaining) if remaining > 0 else 1
            if self.paged:
                while True:
                    got = self.pool.prepare_write(i, s.fed, c)
                    if got is not None:
                        fresh, cow = got
                        for p in fresh:
                            page_reset[p] = True
                        copies.extend(cow)
                        break
                    young = [j for j in order
                             if self._slots[j] is not None
                             and self._slots[j].seq > s.seq]
                    if not young:
                        c = 0    # youngest lane and no pages: park
                        break
                    victim = max(young, key=lambda j: self._slots[j].seq)
                    self._preempt(victim, reset)
            feeds[i] = c
        return feeds

    # -- the iteration ------------------------------------------------------
    def step(self) -> list[Completion]:
        """One continuous-batching iteration; returns requests finished."""
        n = self.pool.n_slots
        C = self.prefill_chunk
        reset = np.zeros((n,), bool)
        page_reset = (np.zeros((self.pool.n_rows,), bool)
                      if self.paged else None)
        copies: list[tuple[int, int]] = []
        # 1. admit into free slots
        self._admit(reset)
        # 2. plan feeds (and, when paged, map blocks / CoW / preempt / park)
        feeds = self._plan(reset, page_reset, copies)
        use_chunk = C > 1 and int(feeds.max(initial=0)) > 1
        width = C if use_chunk else 1
        # a lane needs host-side sampling iff it produces a kept token
        # this step (prompt exhausted after feeding) at temperature > 0
        need_logits = any(
            s is not None and feeds[i] > 0 and s.temperature > 0
            and s.fed + int(feeds[i]) >= s.prompt.size
            for i, s in enumerate(self._slots))
        # 3. assemble slot-indexed inputs
        token = np.zeros((n, width), np.int32)
        pos = np.zeros((n,), np.int32)
        active = np.zeros((n,), bool)
        for i, s in enumerate(self._slots):
            if s is None or feeds[i] == 0:
                continue
            active[i] = True
            pos[i] = s.fed
            if s.fed < s.prompt.size:
                c = int(feeds[i])
                token[i, :c] = s.prompt[s.fed:s.fed + c]
            else:
                token[i, 0] = s.last_token
        # 4. one compiled step for every lane
        args = {"token": token, "pos": pos, "active": active, "reset": reset}
        if self.paged:
            args["block_table"] = self.pool.block_table.copy()
            args["page_reset"] = page_reset
            if self.prefix_cache:
                # static-width CoW row lists; padding dst = n_rows is out
                # of range for the scatter and therefore dropped
                K = self._max_copies
                assert len(copies) <= K, (len(copies), K)
                dst = np.full((K,), self.pool.n_rows, np.int32)
                src = np.zeros((K,), np.int32)
                for j, (d, sp) in enumerate(copies):
                    dst[j], src[j] = d, sp
                args["copy_dst"] = dst
                args["copy_src"] = src
        if use_chunk:
            args["n_tok"] = feeds.astype(np.int32)
        logits = None
        with contextlib.ExitStack() as ctx:
            if self.mesh is not None:
                args = {k: jax.device_put(v, self._in_shardings[k])
                        for k, v in args.items()}
                ctx.enter_context(jax.set_mesh(self.mesh))
                ctx.enter_context(activation_sharding(
                    self._dp, dp_size(self.mesh), "model", self._mp))
            step_fn = self._fn(width, need_logits)
            out = step_fn(
                self.params, self.pool.cache, args["token"], args["pos"],
                args["active"], args["reset"],
                block_table=args.get("block_table"),
                page_reset=args.get("page_reset"),
                n_tok=args.get("n_tok"),
                copy_dst=args.get("copy_dst"),
                copy_src=args.get("copy_src"))
            if need_logits:
                out, logits, self.pool.cache = out
            else:
                out, self.pool.cache = out
        sampled = np.asarray(out).reshape(n)
        if logits is not None:
            logits = np.asarray(logits)
        # 5. account, publish prefixes, sample, evict
        self.stats.steps += 1
        self.stats.slot_steps += n
        done: list[Completion] = []
        for i, s in enumerate(self._slots):
            if s is None or feeds[i] == 0:
                continue
            self.stats.active_slot_steps += 1
            s.fed += int(feeds[i])
            if s.fed < s.prompt.size:
                self.stats.prefill_slot_steps += 1
                continue                      # prompt not exhausted yet
            if self.prefix_cache and not s.published:
                # prefill just completed: the lane's full prompt blocks
                # now hold exactly the shared-prefix KV — index them
                self.pool.publish_prefix(i, s.prompt)
                s.published = True
            if s.temperature > 0:
                key = sampling.request_key(
                    s.seed, s.rid, s.prompt.size + len(s.generated))
                tok = sampling.sample_token(
                    logits[i], temperature=s.temperature, top_k=s.top_k,
                    top_p=s.top_p, key=key)
            else:
                tok = int(sampled[i])
            if s.first_token_step < 0:
                s.first_token_step = self.stats.steps
            s.generated.append(tok)
            s.last_token = tok
            self.stats.tokens_generated += 1
            hit_eos = self.eos_id is not None and tok == self.eos_id
            if hit_eos or len(s.generated) >= s.max_new_tokens:
                done.append(Completion(
                    s.rid, s.prompt, np.asarray(s.generated, np.int32),
                    "eos" if hit_eos else "length", i,
                    s.admitted_step, self.stats.steps, s.first_token_step))
                self._slots[i] = None
                self.pool.release(i)
                self.stats.finished += 1
        # every occupied slot holds KV — parked lanes included (their
        # pages are exactly the ones pinning the pool under pressure)
        live_tokens = sum(s.fed for s in self._slots if s is not None)
        self.stats.kv_token_steps += live_tokens
        self.stats.kv_tokens_live = live_tokens
        self.stats.kv_pages_live = (self.pool.n_live_pages
                                    if self.paged else 0)
        return done

    def run(self, max_steps: Optional[int] = None) -> list[Completion]:
        """Step until drained (or ``max_steps`` *further* iterations —
        relative to this call, so repeated ``run(max_steps=N)`` calls
        each make progress); completions in finish order."""
        out: list[Completion] = []
        start = self.stats.steps
        while self.has_work():
            if max_steps is not None and self.stats.steps - start >= max_steps:
                break
            out.extend(self.step())
        return out
