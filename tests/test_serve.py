"""Continuous-batching engine: slot primitives, parity, reuse, sharding.

The parity contract: under nearest rounding, N staggered requests pushed
through the engine produce token-for-token the same continuations as
lock-step :func:`repro.serve.decode.generate` run per request group with
the cache pinned to the pool length (equal cache shapes ⇒ identical
reduction order ⇒ bitwise-equal logits ⇒ identical argmax). The paged
engine inherits the contract through the block-table view (token at
logical position p sits at gathered index p), and chunked prefill
through per-row causal masks over the same cache axis — both are
asserted here, through page recycling, preemption and the fused kernel.

The 4×2-mesh cases decode with the KV pool sharded over (data, model)
and run only under ``-m dist`` (8 in-process virtual devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import get_policy
from repro.dist import partition as PT
from repro.launch.mesh import make_local_mesh
from repro.models import registry as R
from repro.serve import CachePool, Engine, PagedCachePool, generate, sampling
from repro.serve.cache import (cache_dtype, keep_active, reset_pages,
                               reset_slots, slot_count)

NEAREST = get_policy("bf16_standard")


def _cfg(arch="qwen2.5-3b"):
    return R.get_config(arch).reduced()


def _prompts(rng, sizes, vocab):
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in sizes]


def _parity(engine_done, params, cfg, policy, cache_len):
    """Assert every completion matches lock-step generate token-for-token.

    References are batched per (prompt_len, gen_len) group — one compile
    per shape instead of per request; lanes are numerically independent,
    so the grouping changes nothing."""
    groups = {}
    for c in engine_done:
        groups.setdefault((c.prompt.size, c.tokens.size), []).append(c)
    for (s0, gen), cs in groups.items():
        batch = jnp.asarray(np.stack([c.prompt for c in cs]))
        ref = np.asarray(generate(params, cfg, policy, batch,
                                  max_new_tokens=gen, cache_len=cache_len))
        for i, c in enumerate(cs):
            assert np.array_equal(ref[i, s0:], c.tokens), \
                f"rid {c.rid}: engine {c.tokens} != reference {ref[i, s0:]}"


# ---------------------------------------------------------------------------
# Slot primitives (no model, no compile)
# ---------------------------------------------------------------------------

class TestSlotPrimitives:
    CACHE = {
        "layers": {"b0": (jnp.ones((3, 4, 2, 5, 2), jnp.bfloat16),      # k
                          jnp.ones((3, 4, 2, 5, 2), jnp.bfloat16),      # v
                          jnp.zeros((3, 4, 2), jnp.int32))},            # pos
        "rem": {"b0": {"conv": jnp.ones((4, 3, 6), jnp.bfloat16),
                       "h": jnp.ones((4, 6), jnp.float32)}},
    }

    def test_reset_slots_kills_position_map_not_kv_values(self):
        reset = jnp.asarray([True, False, False, True])
        out = reset_slots(self.CACHE, reset)
        k, _, pos = out["layers"]["b0"]
        # stacked root → slot axis is dim 1; position map −1 makes every
        # stale KV cell unreachable, so the values themselves stay put
        assert int(pos[:, 0].max()) == -1 and int(pos[:, 1].max()) == 0
        assert float(k[:, 0].min()) == 1          # KV pool not streamed
        # unstacked root → slot axis is dim 0; recurrent state is zeroed
        h = out["rem"]["b0"]["h"]
        assert float(jnp.abs(h[0]).max()) == 0 and float(h[1].min()) == 1
        assert float(jnp.abs(out["rem"]["b0"]["conv"][0]).max()) == 0

    def test_keep_active_carries_parked_recurrent_state(self):
        new = jax.tree_util.tree_map(lambda x: x + 1, self.CACHE)
        active = jnp.asarray([True, False, True, False])
        out = keep_active(active, new, self.CACHE)
        conv = out["rem"]["b0"]["conv"]
        assert float(conv[0].min()) == 2 and float(conv[1].max()) == 1
        # attention tuples pass through: parked lanes never write them
        # (pos = −1 routes the scatter out of range at the write site)
        k = out["layers"]["b0"][0]
        assert float(k.min()) == 2

    def test_slot_count_reads_stacked_axis(self):
        assert slot_count(self.CACHE) == 4

    PAGED_CACHE = {
        "layers": {"b0": {"k_pages": jnp.ones((3, 5, 2, 4, 2), jnp.bfloat16),
                          "v_pages": jnp.ones((3, 5, 2, 4, 2), jnp.bfloat16),
                          "pos_pages": jnp.zeros((3, 5, 2), jnp.int32)}},
        "rem": {"b0": {"conv": jnp.ones((4, 3, 6), jnp.bfloat16),
                       "h": jnp.ones((4, 6), jnp.float32)}},
    }

    def test_slot_helpers_skip_paged_leaves(self):
        """Paged leaves are page-indexed: the (N,) slot mask must never
        broadcast against them, and slot_count must not read their row
        extent (5 rows ≠ 4 slots here)."""
        reset = jnp.asarray([True, False, False, True])
        out = reset_slots(self.PAGED_CACHE, reset)
        assert int(out["layers"]["b0"]["pos_pages"].min()) == 0  # untouched
        assert float(jnp.abs(out["rem"]["b0"]["h"][0]).max()) == 0
        new = jax.tree_util.tree_map(lambda x: x + 1, self.PAGED_CACHE)
        kept = keep_active(jnp.asarray([True, False, True, False]),
                           new, self.PAGED_CACHE)
        assert float(kept["layers"]["b0"]["k_pages"].min()) == 2
        assert slot_count(self.PAGED_CACHE) == 4     # from conv, not pages
        with pytest.raises(ValueError):
            slot_count({"layers": {"b0": self.PAGED_CACHE["layers"]["b0"]}})

    def test_reset_pages_kills_position_rows_only(self):
        mask = jnp.asarray([True, False, False, False, True])
        out = reset_pages(self.PAGED_CACHE, mask)
        pp = out["layers"]["b0"]["pos_pages"]        # page dim at index 1
        assert int(pp[:, 0].max()) == -1 and int(pp[:, 4].max()) == -1
        assert int(pp[:, 1].min()) == 0
        assert float(out["layers"]["b0"]["k_pages"].min()) == 1  # values stay
        assert float(out["rem"]["b0"]["conv"].min()) == 1        # slots stay

    def test_serve_input_specs_paged_and_chunked(self):
        class M:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 2}
        specs = PT.serve_input_specs(8, M(), paged=True, n_rows=28, chunk=4)
        assert specs["block_table"] == P(("data",), None)
        assert specs["page_reset"] == P(("data",))   # 28 % 4 == 0
        assert specs["n_tok"] == P(("data",))
        # non-divisible row count replicates the page mask only
        specs = PT.serve_input_specs(8, M(), paged=True, n_rows=27)
        assert specs["page_reset"] == P(None)
        assert specs["token"] == P(("data",), None)

    def test_serve_input_specs_slot_axis(self):
        class M:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 2}
        specs = PT.serve_input_specs(8, M())
        assert specs["token"] == P(("data",), None)
        assert specs["pos"] == P(("data",))
        # non-divisible slot count replicates, matching cache_specs
        assert PT.serve_input_specs(6, M())["pos"] == P(None)


# ---------------------------------------------------------------------------
# Cache pool bookkeeping
# ---------------------------------------------------------------------------

class TestCachePool:
    def test_acquire_release_fifo(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        pool = CachePool(params, cfg, NEAREST, n_slots=3, max_len=16)
        assert [pool.acquire() for _ in range(3)] == [0, 1, 2]
        assert pool.acquire() is None and pool.n_free == 0
        pool.release(1)
        with pytest.raises(ValueError):
            pool.release(1)
        assert pool.acquire() == 1
        assert slot_count(pool.cache) == 3

    def test_value_dtype_follows_policy(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), jnp.float32)
        assert cache_dtype(get_policy("bf16_sr")) == jnp.bfloat16
        assert cache_dtype(get_policy("fp32")) == jnp.float32
        pool = CachePool(params, cfg, get_policy("bf16_sr"),
                         n_slots=2, max_len=8)
        k = pool.cache["layers"]["b0"][0]
        assert k.dtype == jnp.bfloat16
        assert pool.cache["layers"]["b0"][2].dtype == jnp.int32

    def test_submit_validation(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=16)
        with pytest.raises(ValueError):
            eng.submit(np.arange(10, dtype=np.int32), 10)  # 20 > max_len
        with pytest.raises(ValueError):
            eng.submit(np.asarray([], np.int32), 4)


# ---------------------------------------------------------------------------
# Continuous-batching parity + slot reuse
# ---------------------------------------------------------------------------

class TestEngineParity:
    def test_staggered_requests_match_generate(self):
        """8 staggered requests over 3 slots ≡ lock-step generate."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(0)
        eng = Engine(params, cfg, NEAREST, n_slots=3, max_len=24)
        sizes = (5, 7, 5, 7, 5, 7, 5, 7)
        gens = (8, 6, 8, 6, 8, 6, 8, 6)
        for p, g in zip(_prompts(rng, sizes, cfg.vocab), gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 8 and not eng.has_work()
        # 8 admissions onto 3 slots ⇒ eviction + mid-flight refill happened
        assert eng.stats.admitted == 8
        assert {c.slot for c in done} == {0, 1, 2}
        _parity(done, params, cfg, NEAREST, cache_len=24)
        # token accounting adds up
        assert eng.stats.tokens_generated == sum(gens)
        assert eng.stats.slot_steps == eng.stats.steps * 3
        assert 0 < eng.stats.utilization <= 1

    def test_eviction_refill_reuses_slots(self):
        """More waves than slots: every slot is recycled and state never
        leaks across the requests that share it."""
        cfg = _cfg("recurrentgemma-2b")  # RG-LRU state + local-attn ring
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(1)
        eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=16)
        sizes, gens = (4, 6, 4, 6, 4), (5, 4, 6, 4, 5)
        for p, g in zip(_prompts(rng, sizes, cfg.vocab), gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 5 and eng.pool.n_free == 2
        per_slot = {0: 0, 1: 0}
        for c in done:
            per_slot[c.slot] += 1
        assert min(per_slot.values()) >= 2          # both slots recycled
        _parity(done, params, cfg, NEAREST, cache_len=16)

    def test_parity_holds_for_f32_cache_policy(self):
        """Non-bf16 value dtype: generate must build its cache in
        cache_dtype(policy) or KV storage rounding breaks parity."""
        policy = get_policy("fp32")
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
        rng = np.random.default_rng(3)
        eng = Engine(params, cfg, policy, n_slots=2, max_len=24)
        assert eng.pool.dtype == jnp.float32
        for p in _prompts(rng, (5, 5, 5), cfg.vocab):
            eng.submit(p, 16)
        done = eng.run()
        assert len(done) == 3
        _parity(done, params, cfg, policy, cache_len=24)

    def test_eos_evicts_early(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        prompt = np.arange(1, 6, dtype=np.int32)
        free = Engine(params, cfg, NEAREST, n_slots=1, max_len=32)
        free.submit(prompt, 12)
        full = free.run()[0]
        assert full.finish_reason == "length" and full.tokens.size == 12
        eos = int(full.tokens[3])                   # force a mid-stream stop
        cut = int(np.argmax(full.tokens == eos))    # its first occurrence
        eng = Engine(params, cfg, NEAREST, n_slots=1, max_len=32,
                     eos_id=eos)
        eng.submit(prompt, 12)
        c = eng.run()[0]
        assert c.finish_reason == "eos"
        assert c.tokens.tolist() == full.tokens[:cut + 1].tolist()
        assert int(c.tokens[-1]) == eos


# ---------------------------------------------------------------------------
# Sharded decode (8 virtual devices, -m dist)
# ---------------------------------------------------------------------------

@pytest.mark.dist
class TestShardedEngine:
    def test_mesh_4x2_sharded_cache_parity(self, eight_virtual_devices):
        """Engine on a 4 data × 2 model mesh: KV pool sharded on both
        axes, tokens identical to the single-device engine."""
        from jax.sharding import NamedSharding

        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(2)
        sizes = (5, 7, 5, 7, 5, 7, 5, 7, 5, 7)
        gens = (6, 8, 6, 8, 6, 8, 6, 8, 6, 8)
        prompts = _prompts(rng, sizes, cfg.vocab)

        mesh = make_local_mesh(4, 2)
        pspecs = PT.param_specs(params, cfg, mesh)
        params8 = jax.device_put(params, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: hasattr(x, "_normalized_spec_for_aval")))
        eng = Engine(params8, cfg, NEAREST, n_slots=8, max_len=24, mesh=mesh)
        # the slot axis of every KV leaf is sharded over the data axis
        k = eng.pool.cache["layers"]["b0"][0]
        assert k.sharding.spec[1] == "data"         # dim 1: stacked layers
        assert "model" in jax.tree_util.tree_flatten(
            tuple(k.sharding.spec))[0]              # head dim on model
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 10
        _parity(done, params, cfg, NEAREST, cache_len=24)


# ---------------------------------------------------------------------------
# Fused decode attention (Pallas, interpret on CPU) — parity contract
# ---------------------------------------------------------------------------

class TestFusedDecode:
    def test_fused_engine_matches_generate(self):
        """--fused-decode engine ≡ lock-step generate, token for token,
        through admission / parked lanes / eviction / slot reuse."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(7)
        eng = Engine(params, cfg, NEAREST, n_slots=3, max_len=24,
                     fused_decode=True)
        sizes, gens = (5, 7, 5, 7, 5, 7), (8, 6, 8, 6, 8, 6)
        for p, g in zip(_prompts(rng, sizes, cfg.vocab), gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 6
        _parity(done, params, cfg, NEAREST, cache_len=24)

    def test_fused_engine_matches_plain_engine(self):
        """Same stream through fused and generic engines: identical
        completions (stronger than parity with generate — covers parked
        lanes on the same step schedule)."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(8)
        prompts = _prompts(rng, (4, 6, 5, 7), cfg.vocab)
        outs = []
        for fused in (False, True):
            eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=20,
                         fused_decode=fused)
            for p in prompts:
                eng.submit(p, 6)
            outs.append({c.rid: c.tokens.tolist() for c in eng.run()})
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Paged pool bookkeeping (no model compile)
# ---------------------------------------------------------------------------

class TestPagedPool:
    def _pool(self, **kw):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        kw.setdefault("n_slots", 3)
        kw.setdefault("max_len", 32)
        kw.setdefault("page_size", 8)
        return PagedCachePool(params, cfg, NEAREST, **kw)

    def test_alloc_free_invariants(self):
        pool = self._pool()                        # 3 slots × 4 blocks
        assert pool.n_pages == 12 and pool.null_page == pool.n_rows - 1
        s = pool.acquire()
        fresh = pool.ensure_blocks(s, 17)          # positions 0..17 → 3 pages
        assert len(fresh) == 3 and pool.n_live_pages == 3
        assert pool.ensure_blocks(s, 17) == []     # already covered
        pool.check_invariants()
        # pages are disjoint across lanes
        s2 = pool.acquire()
        fresh2 = pool.ensure_blocks(s2, 31)
        assert len(fresh2) == 4 and not set(fresh) & set(fresh2)
        pool.check_invariants()
        # release returns every page — nothing leaks
        pool.release(s)
        assert pool.n_live_pages == 4
        assert (pool.block_table[s] == pool.null_page).all()
        pool.release(s2)
        assert pool.n_live_pages == 0 and pool.n_free_pages == pool.n_pages
        pool.check_invariants()

    def test_exhaustion_takes_nothing(self):
        pool = self._pool(n_pages=5)
        a, b = pool.acquire(), pool.acquire()
        assert pool.ensure_blocks(a, 31) is not None    # 4 of 5 pages
        before = pool.n_free_pages
        assert pool.ensure_blocks(b, 15) is None        # needs 2, has 1
        assert pool.n_free_pages == before              # all-or-nothing
        pool.check_invariants()

    def test_pool_validation_and_capacity(self):
        with pytest.raises(ValueError):
            self._pool(n_pages=3)                  # < blocks per sequence
        pool = self._pool(n_pages=6)
        assert pool.capacity_tokens == 48
        assert pool.max_blocks == 4

    def test_paged_nbytes_scale_with_pages_not_slots(self):
        """Equal token budget ⇒ equal KV bytes; fewer pages ⇒ fewer bytes
        even with more slots (the memory win paging exists for)."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        contig = CachePool(params, cfg, NEAREST, n_slots=3, max_len=32)
        full = self._pool()                        # same 96-token budget
        half = self._pool(n_slots=6, n_pages=6)    # 2× slots, half the pages
        kv = lambda c: sum(l.size * l.dtype.itemsize for l in
                           jax.tree_util.tree_leaves(c)
                           if l.dtype != jnp.int32)
        # paged pool carries one extra (null) page per layer
        per_page = kv(full.cache) / (full.n_rows)
        assert abs(kv(full.cache) - kv(contig.cache)) <= per_page * 2
        assert kv(half.cache) < kv(full.cache)


# ---------------------------------------------------------------------------
# Paged engine parity (token-for-token vs generate, page recycling)
# ---------------------------------------------------------------------------

class TestPagedEngine:
    def test_paged_engine_matches_generate(self):
        """Paged engine ≡ lock-step generate through admission, page
        alloc as sequences grow, eviction and page recycling (8 requests
        over 3 slots — every slot and most pages are reused)."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(10)
        eng = Engine(params, cfg, NEAREST, n_slots=3, max_len=24,
                     paged=True, page_size=8)
        sizes, gens = (5, 7, 5, 7, 5, 7, 5, 7), (8, 6, 8, 6, 8, 6, 8, 6)
        for p, g in zip(_prompts(rng, sizes, cfg.vocab), gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 8 and not eng.has_work()
        _parity(done, params, cfg, NEAREST, cache_len=24)
        eng.pool.check_invariants()
        # drained ⇒ no leak: the only live pages are prefix-index holds
        assert eng.pool.n_live_pages == eng.pool.n_cached_pages
        eng.pool.clear_prefix()
        assert eng.pool.n_live_pages == 0

    def test_preemption_under_page_pressure(self):
        """An undersubscribed pool forces mid-flight preemption; greedy
        determinism means the preempted request still finishes with the
        exact reference tokens, and no page is double-assigned."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(11)
        eng = Engine(params, cfg, NEAREST, n_slots=4, max_len=32,
                     paged=True, page_size=8, n_pages=6)  # 48 of 128 tokens
        sizes, gens = (5, 9, 3, 12, 7), (6, 4, 8, 5, 6)
        for p, g in zip(_prompts(rng, sizes, cfg.vocab), gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 5
        assert eng.stats.preemptions >= 1
        _parity(done, params, cfg, NEAREST, cache_len=32)
        eng.pool.check_invariants()
        assert eng.pool.n_live_pages == eng.pool.n_cached_pages
        eng.pool.clear_prefix()
        assert eng.pool.n_live_pages == 0

    def test_paged_fused_matches_plain_paged(self):
        """Fused paged Pallas kernel ≡ generic gathered path on the same
        step schedule (covers parked lanes + null-page masking)."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(12)
        prompts = _prompts(rng, (4, 6, 5, 7), cfg.vocab)
        outs = []
        for fused in (False, True):
            eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=24,
                         paged=True, page_size=8, fused_decode=fused)
            for p in prompts:
                eng.submit(p, 6)
            outs.append({c.rid: c.tokens.tolist() for c in eng.run()})
        assert outs[0] == outs[1]

    def test_utilization_reports_live_tokens(self):
        """A short sequence alone in a big pool must report *token*
        utilization (~its length / capacity), not lane occupancy."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        eng = Engine(params, cfg, NEAREST, n_slots=4, max_len=64,
                     paged=True, page_size=8)
        eng.submit(np.arange(1, 6, dtype=np.int32), 4)   # ≤ 9 live tokens
        eng.run()
        assert eng.stats.kv_capacity_tokens == 4 * 64
        assert 0 < eng.stats.utilization < 9 / 256 + 1e-9
        assert eng.stats.lane_occupancy <= 0.25


# ---------------------------------------------------------------------------
# Chunked prefill parity
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_chunked_prefill_matches_generate(self):
        """Prompts longer than one chunk, fed C at a time interleaved
        with decodes, still match generate token-for-token — contiguous
        and paged."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(13)
        sizes, gens = (13, 5, 17, 9, 13, 5), (6, 8, 4, 6, 6, 8)
        prompts = _prompts(rng, sizes, cfg.vocab)
        for paged in (False, True):
            eng = Engine(params, cfg, NEAREST, n_slots=3, max_len=24,
                         paged=paged, page_size=8, prefill_chunk=4)
            for p, g in zip(prompts, gens):
                eng.submit(p, g)
            done = eng.run()
            assert len(done) == 6
            _parity(done, params, cfg, NEAREST, cache_len=24)

    def test_chunking_cuts_prefill_steps(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(14)
        prompts = _prompts(rng, (16, 16), cfg.vocab)
        steps = {}
        for chunk in (1, 8):
            eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=24,
                         paged=True, page_size=8, prefill_chunk=chunk)
            for p in prompts:
                eng.submit(p, 4)
            done = eng.run()
            assert len(done) == 2
            steps[chunk] = eng.stats.steps
        # 16-token prompt: 16 prefill steps unchunked vs 2 chunked
        assert steps[8] < steps[1] - 8

    def test_chunked_prefill_rejects_recurrent_stacks(self):
        cfg = _cfg("recurrentgemma-2b")
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        with pytest.raises(ValueError, match="attention-only"):
            Engine(params, cfg, NEAREST, n_slots=2, max_len=16,
                   prefill_chunk=4)


@pytest.mark.dist
class TestShardedPagedEngine:
    def test_mesh_4x2_paged_fused_parity(self, eight_virtual_devices):
        """Paged engine + fused decode kernel on a 4 data × 2 model mesh:
        page pool sharded over (data → page rows, model → head dim),
        tokens identical to single-device generate."""
        from jax.sharding import NamedSharding

        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(15)
        sizes = (5, 7, 5, 7, 5, 7, 5, 7, 5, 7)
        gens = (6, 8, 6, 8, 6, 8, 6, 8, 6, 8)
        prompts = _prompts(rng, sizes, cfg.vocab)

        mesh = make_local_mesh(4, 2)
        pspecs = PT.param_specs(params, cfg, mesh)
        params8 = jax.device_put(params, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: hasattr(x, "_normalized_spec_for_aval")))
        eng = Engine(params8, cfg, NEAREST, n_slots=8, max_len=24,
                     mesh=mesh, paged=True, page_size=8, fused_decode=True,
                     prefill_chunk=4)
        assert eng.pool.n_rows % 4 == 0            # padded for the dp axes
        kp = eng.pool.cache["layers"]["b0"]["k_pages"]
        assert kp.sharding.spec[1] == "data"       # page rows on data
        assert "model" in jax.tree_util.tree_flatten(
            tuple(kp.sharding.spec))[0]            # head dim on model
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 10
        _parity(done, params, cfg, NEAREST, cache_len=24)
        eng.pool.check_invariants()
        assert eng.pool.n_live_pages == eng.pool.n_cached_pages
        eng.pool.clear_prefix()
        assert eng.pool.n_live_pages == 0


@pytest.mark.dist
class TestShardedFusedDecode:
    def test_mesh_4x2_fused_decode_parity(self, eight_virtual_devices):
        """Fused Pallas decode inside the GSPMD-partitioned serve step
        (4 data × 2 model mesh, KV pool sharded on both axes)."""
        from jax.sharding import NamedSharding

        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(9)
        sizes = (5, 7, 5, 7, 5, 7, 5, 7, 5, 7)
        gens = (6, 8, 6, 8, 6, 8, 6, 8, 6, 8)
        prompts = _prompts(rng, sizes, cfg.vocab)

        mesh = make_local_mesh(4, 2)
        pspecs = PT.param_specs(params, cfg, mesh)
        params8 = jax.device_put(params, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: hasattr(x, "_normalized_spec_for_aval")))
        eng = Engine(params8, cfg, NEAREST, n_slots=8, max_len=24,
                     mesh=mesh, fused_decode=True)
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        done = eng.run()
        assert len(done) == 10
        _parity(done, params, cfg, NEAREST, cache_len=24)


# ---------------------------------------------------------------------------
# Per-request sampling (determinism, greedy coexistence, preemption)
# ---------------------------------------------------------------------------

class TestSampling:
    def test_filters_restrict_support_to_argmax(self):
        """top_k=1 and a vanishing top_p both collapse to the argmax
        token no matter the gumbel draw."""
        logits = np.asarray([0.1, 2.0, -1.0, 1.9, 0.0], np.float32)
        for kw in ({"top_k": 1}, {"top_p": 1e-6}):
            for trial in range(5):
                key = sampling.request_key(0, 7, trial)
                assert sampling.sample_token(
                    logits, temperature=1.0, key=key, **kw) == 1

    def test_sampling_deterministic_per_seed_and_rid(self):
        """Same (seed, rid) reproduces the continuation across engine
        instances; a different seed decodes a different one."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        prompt = np.random.default_rng(20).integers(
            0, cfg.vocab, size=6).astype(np.int32)

        def run_once(seed):
            eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=24)
            eng.submit(prompt, 10, rid=7, temperature=1.0, seed=seed)
            return eng.run()[0].tokens.tolist()

        assert run_once(3) == run_once(3)
        assert run_once(3) != run_once(4)

    def test_greedy_lanes_bitwise_unchanged_next_to_sampling(self):
        """Greedy requests sharing steps with a sampling lane still match
        generate token-for-token (the logits-returning executable keeps
        the in-graph argmax path byte-identical)."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(21)
        prompts = _prompts(rng, (5, 5, 5, 5), cfg.vocab)
        eng = Engine(params, cfg, NEAREST, n_slots=4, max_len=24)
        for i, p in enumerate(prompts[:3]):
            eng.submit(p, 8)                        # greedy lanes
        eng.submit(prompts[3], 8, temperature=0.9, seed=1)
        done = eng.run()
        assert len(done) == 4
        greedy = [c for c in done if c.rid < 3]
        _parity(greedy, params, cfg, NEAREST, cache_len=24)

    def test_temperature_zero_is_greedy(self):
        """temperature=0 (whatever top-k/top-p say) takes the greedy
        path exactly."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        prompt = np.random.default_rng(22).integers(
            0, cfg.vocab, size=5).astype(np.int32)
        outs = []
        for kw in ({}, {"temperature": 0.0, "top_k": 5, "top_p": 0.5}):
            eng = Engine(params, cfg, NEAREST, n_slots=1, max_len=16)
            eng.submit(prompt, 8, **kw)
            outs.append(eng.run()[0].tokens.tolist())
        assert outs[0] == outs[1]

    def test_sampling_survives_recompute_preemption(self):
        """A sampled request preempted for pages regenerates the exact
        same tokens: logits are bitwise reproducible and the PRNG key is
        a pure function of (seed, rid, position)."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(23)
        prompts = _prompts(rng, (5, 9, 3, 12, 7), cfg.vocab)
        gens = (6, 4, 8, 5, 6)
        outs = {}
        for tag, n_pages in (("tight", 6), ("roomy", None)):
            eng = Engine(params, cfg, NEAREST, n_slots=4, max_len=32,
                         paged=True, page_size=8, n_pages=n_pages)
            for i, (p, g) in enumerate(zip(prompts, gens)):
                eng.submit(p, g, rid=i, temperature=0.8, top_k=20, seed=5)
            done = eng.run()
            assert len(done) == 5
            if tag == "tight":
                assert eng.stats.preemptions >= 1
            outs[tag] = {c.rid: c.tokens.tolist() for c in done}
        assert outs["tight"] == outs["roomy"]

    def test_submit_validates_sampling_params(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        eng = Engine(params, cfg, NEAREST, n_slots=1, max_len=16)
        prompt = np.arange(1, 5, dtype=np.int32)
        for kw in ({"temperature": -0.1}, {"top_k": -1},
                   {"top_p": 0.0}, {"top_p": 1.5}):
            with pytest.raises(ValueError):
                eng.submit(prompt, 4, **kw)


# ---------------------------------------------------------------------------
# Prefix cache (hash-chain sharing, copy-on-write, eviction)
# ---------------------------------------------------------------------------

class TestPrefixCache:
    def _pool(self, **kw):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        kw.setdefault("n_slots", 3)
        kw.setdefault("max_len", 32)
        kw.setdefault("page_size", 8)
        return PagedCachePool(params, cfg, NEAREST, **kw)

    def test_refcounted_sharing_and_cow_bookkeeping(self):
        """publish → match → adopt shares physical pages across holders;
        a write into a shared block CoW-remaps only the written block."""
        pool = self._pool()
        prompt = np.arange(100, 116, dtype=np.int32)   # 2 full blocks
        a = pool.acquire()
        assert len(pool.ensure_blocks(a, 15)) == 2
        assert pool.publish_prefix(a, prompt) == 2
        assert pool.n_cached_pages == 2
        pool.check_invariants()
        matched = pool.match_prefix(prompt)
        assert len(matched) == 2
        assert pool.match_prefix(prompt[:8]).__len__() == 1  # shorter prefix
        assert pool.match_prefix(prompt[::-1]) == []         # different tokens
        b = pool.acquire()
        pool.adopt_prefix(b, matched)
        assert pool.block_table[b][0] == pool.block_table[a][0]
        pool.check_invariants()
        pool.release(a)                     # index + lane b keep the pages
        assert pool.n_live_pages == 2
        # b writes position 15 → shared block 1 CoW-remaps, block 0 stays
        fresh, copies = pool.prepare_write(b, 15, 1)
        assert fresh == [] and len(copies) == 1
        dst, src = copies[0]
        assert src == matched[1] and pool.block_table[b][1] == dst
        assert pool.block_table[b][0] == matched[0]     # still shared
        pool.check_invariants()
        pool.release(b)
        assert pool.n_live_pages == pool.n_cached_pages == 2
        assert pool.clear_prefix() == 2
        assert pool.n_live_pages == 0
        pool.check_invariants()

    def test_lru_reclaim_frees_cached_pages_under_pressure(self):
        """Index-only pages are reclaimed (oldest first) when the free
        list cannot cover an allocation — cached prefixes never starve
        live lanes."""
        pool = self._pool(n_slots=2, max_len=32, n_pages=4)
        a = pool.acquire()
        pool.ensure_blocks(a, 15)
        pool.publish_prefix(a, np.arange(16, dtype=np.int32))
        pool.release(a)
        assert pool.n_free_pages == 2 and pool.n_reclaimable() == 2
        b = pool.acquire()
        fresh = pool.ensure_blocks(b, 31)   # needs all 4 pages
        assert fresh is not None and len(fresh) == 4
        assert pool.n_cached_pages == 0     # cache evicted to make room
        pool.check_invariants()

    def test_shared_prompt_skips_prefill_and_keeps_greedy_tokens(self):
        """Second request with the same system prompt skips the cached
        blocks' prefill steps and still decodes the exact greedy tokens."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(30)
        system = rng.integers(0, cfg.vocab, size=16).astype(np.int32)
        tails = _prompts(rng, (4, 4), cfg.vocab)
        prompts = [np.concatenate([system, t]) for t in tails]
        outs = {}
        steps = {}
        for on in (True, False):
            eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=32,
                         paged=True, page_size=8, prefix_cache=on)
            assert eng.prefix_cache is on
            eng.submit(prompts[0], 6)
            eng.run()                       # drain: prefix now published
            eng.submit(prompts[1], 6)
            before = eng.stats.prefill_slot_steps
            done = eng.run()
            outs[on] = {c.rid: c.tokens.tolist() for c in done}
            steps[on] = eng.stats.prefill_slot_steps - before
            if on:
                assert eng.stats.prefix_hits == 1
                assert eng.stats.prefix_tokens_reused == 16
                eng.pool.check_invariants()
        # 16 of 20 prompt tokens came from the cache
        assert steps[True] == steps[False] - 16
        assert outs[True] == outs[False]

    def test_full_prompt_match_refeeds_last_token_via_cow(self):
        """An identical prompt (whole prompt in full blocks) re-feeds
        only its last token — the write CoW-remaps the shared final
        block — and reproduces the greedy continuation."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        prompt = np.random.default_rng(31).integers(
            0, cfg.vocab, size=16).astype(np.int32)   # 2 full blocks
        eng = Engine(params, cfg, NEAREST, n_slots=1, max_len=32,
                     paged=True, page_size=8)
        eng.submit(prompt, 6)
        first = eng.run()[0]
        eng.submit(prompt, 6)
        before = eng.stats.prefill_slot_steps
        again = eng.run()[0]
        assert eng.stats.prefix_hits == 1
        assert eng.stats.prefix_tokens_reused == 15   # all but the last token
        assert eng.stats.prefill_slot_steps == before  # no prefill steps left
        assert again.tokens.tolist() == first.tokens.tolist()
        eng.pool.check_invariants()

    def test_prefix_cache_gating(self):
        cfg = _cfg("recurrentgemma-2b")     # ring-window + recurrent state
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        atn = _cfg()
        params_atn = R.init(atn, jax.random.PRNGKey(0), NEAREST.param_dtype)
        with pytest.raises(ValueError, match="paged"):
            Engine(params_atn, atn, NEAREST, n_slots=2, max_len=16,
                   prefix_cache=True)
        with pytest.raises(ValueError):
            Engine(params, cfg, NEAREST, n_slots=2, max_len=16,
                   paged=True, prefix_cache=True)
        eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=16, paged=True)
        assert eng.prefix_cache is False    # auto-off on ineligible stacks


# ---------------------------------------------------------------------------
# Engine accounting fixes (live-KV, TTFT across preemption, run, rids)
# ---------------------------------------------------------------------------

class TestEngineAccounting:
    def test_parked_lanes_count_in_live_kv(self):
        """A lane parked for pages still holds its KV — live-token stats
        must include it (they are exactly the tokens pinning the pool)."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(40)
        eng = Engine(params, cfg, NEAREST, n_slots=2, max_len=12,
                     paged=True, page_size=4, n_pages=3, prefix_cache=False)
        eng.submit(rng.integers(0, cfg.vocab, 4).astype(np.int32), 4)
        eng.submit(rng.integers(0, cfg.vocab, 4).astype(np.int32), 8)
        parked_seen = False
        while eng.has_work():
            fed_before = {i: s.fed for i, s in enumerate(eng._slots) if s}
            eng.step()
            eng.pool.check_invariants()
            live = sum(s.fed for s in eng._slots if s is not None)
            assert eng.stats.kv_tokens_live == live
            for i, s in enumerate(eng._slots):
                if s is not None and fed_before.get(i) == s.fed:
                    parked_seen = True     # occupied lane fed nothing
        assert parked_seen
        assert eng.stats.finished == 2

    def test_ttft_and_admitted_span_preemption(self):
        """Preempted requests keep their original admitted/first-token
        steps, and ``admitted`` counts requests — not admission events."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(41)
        eng = Engine(params, cfg, NEAREST, n_slots=4, max_len=32,
                     paged=True, page_size=8, n_pages=6)
        for p, g in zip(_prompts(rng, (5, 9, 3, 12, 7), cfg.vocab),
                        (6, 4, 8, 5, 6)):
            eng.submit(p, g)
        first_admit: dict = {}
        first_tok: dict = {}
        done = []
        while eng.has_work():
            done.extend(eng.step())
            for s in eng._slots:
                if s is None:
                    continue
                first_admit.setdefault(s.rid, s.admitted_step)
                if s.generated and s.rid not in first_tok:
                    first_tok[s.rid] = eng.stats.steps
        assert eng.stats.preemptions >= 1
        assert eng.stats.admitted == 5      # once per request, not per admit
        for c in done:
            assert c.admitted_step == first_admit[c.rid]
            assert c.first_token_step == first_tok[c.rid]

    def test_run_max_steps_is_relative_to_the_call(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        eng = Engine(params, cfg, NEAREST, n_slots=1, max_len=32)
        eng.submit(np.arange(1, 6, dtype=np.int32), 20)
        eng.run(max_steps=3)
        assert eng.stats.steps == 3 and eng.has_work()
        eng.run(max_steps=3)                # must make progress, not no-op
        assert eng.stats.steps == 6
        done = eng.run()
        assert len(done) == 1 and not eng.has_work()

    def test_rid_collision_rejected_while_pending_or_in_flight(self):
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        eng = Engine(params, cfg, NEAREST, n_slots=1, max_len=16)
        prompt = np.arange(1, 5, dtype=np.int32)
        eng.submit(prompt, 4, rid=5)
        with pytest.raises(ValueError, match="rid 5"):
            eng.submit(prompt, 4, rid=5)    # collides while pending
        eng.step()                          # admitted into a slot
        with pytest.raises(ValueError, match="rid 5"):
            eng.submit(prompt, 4, rid=5)    # collides while in flight
        eng.run()
        assert eng.submit(prompt, 4, rid=5) == 5   # finished: rid reusable
        eng.run()


# ---------------------------------------------------------------------------
# Preemption storm (invariants every step, refcounts drain)
# ---------------------------------------------------------------------------

class TestPreemptionStorm:
    def test_storm_holds_invariants_every_step(self):
        """Tiny page pool + long prompts: repeated preemption, parking
        and prefix sharing, with pool invariants checked after every
        single engine step and refcounts draining to zero at the end."""
        cfg = _cfg()
        params = R.init(cfg, jax.random.PRNGKey(0), NEAREST.param_dtype)
        rng = np.random.default_rng(42)
        eng = Engine(params, cfg, NEAREST, n_slots=4, max_len=24,
                     paged=True, page_size=4, n_pages=10, prefill_chunk=4)
        sizes, gens = (12, 10, 14, 9, 11, 13), (6, 8, 5, 7, 6, 5)
        for i, (p, g) in enumerate(zip(_prompts(rng, sizes, cfg.vocab),
                                       gens)):
            # mix greedy and sampled lanes through the same storm
            kw = {"temperature": 0.7, "seed": 9} if i % 3 == 2 else {}
            eng.submit(p, g, **kw)
        done = []
        while eng.has_work():
            done.extend(eng.step())
            eng.pool.check_invariants()
            live = sum(s.fed for s in eng._slots if s is not None)
            assert eng.stats.kv_tokens_live == live
        assert len(done) == 6
        assert eng.stats.preemptions >= 1
        assert eng.stats.admitted == 6
        for c in done:                      # TTFT ordering sane throughout
            assert c.admitted_step <= c.first_token_step <= c.finished_step
        _parity([c for i, c in enumerate(sorted(done, key=lambda c: c.rid))
                 if c.rid % 3 != 2], params, cfg, NEAREST, cache_len=24)
        # refcounts drain: only index holds survive, then nothing
        assert eng.pool.n_live_pages == eng.pool.n_cached_pages
        eng.pool.clear_prefix()
        assert eng.pool.n_live_pages == 0
        assert int(eng.pool._ref.sum()) == 0
        eng.pool.check_invariants()
