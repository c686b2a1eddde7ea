"""Prefix caching: allocator trie semantics, tail-prefill numerical parity,
and end-to-end reuse across engine requests (the O(n²)→O(n) fix for the
ReAct loop's resend-everything pattern, SURVEY.md §5/§7)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from opsagent_tpu.serving.kvcache import OutOfPages, PageAllocator


P = 4  # page size for allocator tests


def toks(n, base=0):
    return [base + i for i in range(n)]


class TestAllocatorTrie:
    def test_roundtrip_match_after_free(self):
        a = PageAllocator(num_pages=16, page_size=P, max_pages_per_seq=8)
        sid = a.allocate(10)  # 3 pages, 2 full
        assert a.match_prefix(toks(10)) == []
        a.free(sid, tokens=toks(10))
        pages = a.match_prefix(toks(10))
        assert len(pages) == 2  # only full pages cached
        # Shorter and longer prompts match the right amount.
        assert len(a.match_prefix(toks(4))) == 1
        assert len(a.match_prefix(toks(3))) == 0
        assert len(a.match_prefix(toks(30))) == 2
        # Different content: no match.
        assert a.match_prefix(toks(10, base=100)) == []

    def test_shared_allocation_and_refcount(self):
        a = PageAllocator(num_pages=8, page_size=P, max_pages_per_seq=8)
        s1 = a.allocate(8)
        a.free(s1, tokens=toks(8))          # 2 cached pages
        prefix = a.match_prefix(toks(8))
        s2 = a.allocate(9, prefix_pages=prefix)
        # 2 shared + 1 fresh page.
        assert a._seqs[s2].num_shared == 2
        assert a.hit_tokens == 8
        # Shared pages are pinned: exhaust the pool (2 shared + 1 fresh used,
        # 5 free), eviction must not touch the refcounted pages.
        s3 = a.allocate(20)  # 5 pages
        with pytest.raises(OutOfPages):
            a.allocate(4)
        a.free(s3)
        a.free(s2, tokens=toks(9))

    def test_eviction_lru_leaves_first(self):
        a = PageAllocator(num_pages=4, page_size=P, max_pages_per_seq=4)
        s1 = a.allocate(8)
        a.free(s1, tokens=toks(8))           # cache chain: pg A <- pg B
        s2 = a.allocate(8, prefix_pages=a.match_prefix(toks(8)))
        a.free(s2, tokens=toks(8))           # still 2 cached, 2 free
        # Allocating 3 pages forces one eviction: the LEAF (second page)
        # must go before its parent.
        s3 = a.allocate(12, prefix_pages=[])
        assert len(a.match_prefix(toks(8))) == 1   # parent survived
        a.free(s3)

    @pytest.mark.parametrize("state", [0, 3], ids=["pages", "state-slots"])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_queues_pick_what_a_scan_of_the_trie_picks(self, state, seed):
        """The LRU queues and the kept count against their oracle, a scan
        of the whole trie: under random admissions over shared prefixes,
        growth, frees and promotions on a pool that runs dry, both hand
        out the same pages and snapshot slots, and ``free_pages`` is the
        scan's count at every step."""
        import random

        class Scan(PageAllocator):
            def _take_free_page(self):
                if self._free:
                    return self._free.pop()
                victim = None
                for node in self._by_page.values():
                    if node.refcount == 0 and node.children == 0 and (
                            victim is None
                            or node.last_use < victim.last_use):
                        victim = node
                if victim is None:
                    raise OutOfPages("dry")
                self._evict(victim)
                return self._free.pop()

            def _take_snapshot_slot(self, keep=-1):
                if self._free_snaps:
                    return self._free_snaps.pop()
                victim = None
                for node in self._by_page.values():
                    if node.snapshot >= 0 and node.snapshot != keep and (
                            victim is None
                            or node.last_use < victim.last_use):
                        victim = node
                if victim is None:
                    return -1
                slot, victim.snapshot = victim.snapshot, -1
                self.snapshots_evicted += 1
                return slot

        def scanned_free(a):
            return len(a._free) + sum(
                1 for n in a._by_page.values()
                if n.refcount == 0 and n.children == 0)

        kw = dict(num_pages=40, page_size=P, max_pages_per_seq=12,
                  state_slots=state, state_snapshots=2 * state)
        new, old = PageAllocator(**kw), Scan(**kw)
        rng = random.Random(seed)
        stems = [toks(8, base=1000 * k) for k in range(3)]
        live: dict[int, list[int]] = {}
        for step in range(600):
            op = rng.random()
            if op < 0.45 and len(live) < (state or 6):
                prompt = rng.choice(stems) + toks(
                    rng.randrange(1, 20), base=rng.randrange(8) * 50)
                got = []
                for a in (new, old):
                    if state:
                        pages, _slot, _full = a.match_prefix_state(
                            prompt[:-1])
                    else:
                        pages = a.match_prefix(prompt[:-1])
                    try:
                        sid = a.allocate(len(prompt), prefix_pages=pages)
                        got.append((sid, a.page_table_row(sid).tolist()))
                    except OutOfPages:
                        got.append(None)
                assert got[0] == got[1], step
                if got[0] is not None:
                    live[got[0][0]] = prompt
            elif op < 0.65 and live:
                sid = rng.choice(list(live))
                n = rng.randrange(1, 6)
                grown = [a.extend_upto(sid, n) for a in (new, old)]
                assert grown[0] == grown[1], step
                live[sid] = live[sid] + toks(grown[0], base=7000 + step)
                for a in (new, old):
                    a.note_pass(sid, 0, len(live[sid]), each_token=True)
            elif op < 0.75 and live and not state:
                sid = rng.choice(list(live))
                assert (new.promote_prefix(sid, live[sid])
                        == old.promote_prefix(sid, live[sid])), step
            elif live:
                sid = rng.choice(list(live))
                history = live.pop(sid)
                tokens = history if rng.random() < 0.9 else None
                for a in (new, old):
                    a.free(sid, tokens=tokens)
            assert new.free_pages == scanned_free(new) == scanned_free(old)
            assert new.accounting() == old.accounting(), step
            assert [new.pages_of(s) for s in live] == [
                old.pages_of(s) for s in live], step
        assert new.evictions == old.evictions > 20
        assert new.snapshots_evicted == old.snapshots_evicted
        if state:
            assert new.snapshots_evicted > 5

    def test_disabled_cache_frees_everything(self):
        a = PageAllocator(8, P, 8, prefix_cache=False)
        sid = a.allocate(8)
        a.free(sid, tokens=toks(8))
        assert a.match_prefix(toks(8)) == []
        assert len(a._free) == 8


class TestTailPrefillParity:
    def test_prefill_with_prefix_matches_full_prefill(self):
        from opsagent_tpu.models import llama
        from opsagent_tpu.models.config import get_config_preset

        cfg = get_config_preset("tiny-test")
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        PS, NP, MaxP = 8, 16, 6
        rng = np.random.default_rng(3)
        n = 29               # 3 full pages (24) + 5-token tail
        prompt = rng.integers(1, cfg.vocab_size, n).astype(np.int32)

        # Path A: one full prefill.
        cache_a = llama.make_cache(cfg, NP, PS, dtype=jnp.float32)
        table_a = np.full((1, MaxP), -1, np.int32)
        table_a[0, :4] = [0, 1, 2, 3]
        S = 32
        tok_a = np.zeros((1, S), np.int32)
        tok_a[0, :n] = prompt
        logits_a, cache_a = llama.prefill(
            params, cfg, jnp.asarray(tok_a), jnp.asarray([n], jnp.int32),
            cache_a, jnp.asarray(table_a), dtype=jnp.float32,
        )

        # Path B: prefill the 24-token prefix, then tail via
        # prefill_with_prefix into the same pages.
        cache_b = llama.make_cache(cfg, NP, PS, dtype=jnp.float32)
        table_b = np.full((1, MaxP), -1, np.int32)
        table_b[0, :4] = [5, 6, 7, 8]
        tok_p = np.zeros((1, 24), np.int32)
        tok_p[0, :] = prompt[:24]
        _, cache_b = llama.prefill(
            params, cfg, jnp.asarray(tok_p), jnp.asarray([24], jnp.int32),
            cache_b, jnp.asarray(table_b), dtype=jnp.float32,
        )
        tok_t = np.zeros((1, 8), np.int32)
        tok_t[0, :5] = prompt[24:]
        logits_b, cache_b = llama.prefill_with_prefix(
            params, cfg, jnp.asarray(tok_t),
            jnp.asarray([24], jnp.int32), jnp.asarray([5], jnp.int32),
            cache_b, jnp.asarray(table_b), dtype=jnp.float32,
        )
        np.testing.assert_allclose(
            np.asarray(logits_a), np.asarray(logits_b), rtol=2e-4, atol=2e-4
        )
        # KV written by the tail matches the full-prefill KV (same tokens,
        # same positions, different pages).
        ka = np.asarray(cache_a["k"])[:, table_a[0, 3]]
        kb = np.asarray(cache_b["k"])[:, table_b[0, 3]]
        np.testing.assert_allclose(ka[:, :5], kb[:, :5], rtol=2e-4, atol=2e-4)


class TestEnginePrefixReuse:
    @pytest.fixture()
    def engine(self):
        from opsagent_tpu.serving.engine import Engine, EngineConfig

        return Engine(EngineConfig(
            model="tiny-test", dtype=jnp.float32, page_size=8, num_pages=64,
            max_pages_per_seq=16, max_batch_size=2,
            prefill_buckets=(16, 32, 64), max_new_tokens_default=8,
        ))

    def test_repeat_prompt_hits_cache_and_matches(self, engine):
        from opsagent_tpu.serving.sampler import SamplingParams

        rng = np.random.default_rng(0)
        prompt = rng.integers(1, engine.model_cfg.vocab_size, 30).tolist()
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        out1 = engine.generate([prompt], sp)[0]
        assert engine.alloc.hit_tokens == 0
        out2 = engine.generate([prompt], sp)[0]
        assert engine.alloc.hit_tokens >= 24  # ≥3 pages of 8 reused
        assert out1 == out2                  # greedy determinism across reuse

    def test_growing_history_reuses_previous_turns(self, engine):
        """The ReAct pattern: each request = previous history + new text."""
        from opsagent_tpu.serving.sampler import SamplingParams

        rng = np.random.default_rng(1)
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        history = rng.integers(1, 200, 24).tolist()
        engine.generate([history], sp)
        before = engine.alloc.hit_tokens
        history2 = history + rng.integers(1, 200, 24).tolist()
        engine.generate([history2], sp)
        assert engine.alloc.hit_tokens - before >= 16
        before = engine.alloc.hit_tokens
        history3 = history2 + rng.integers(1, 200, 24).tolist()
        engine.generate([history3], sp)
        assert engine.alloc.hit_tokens - before >= 40

    def test_cache_off_still_correct(self):
        from opsagent_tpu.serving.engine import Engine, EngineConfig
        from opsagent_tpu.serving.sampler import SamplingParams

        eng = Engine(EngineConfig(
            model="tiny-test", dtype=jnp.float32, page_size=8, num_pages=64,
            max_pages_per_seq=16, max_batch_size=2,
            prefill_buckets=(16, 32, 64), prefix_cache=False,
        ))
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, eng.model_cfg.vocab_size, 30).tolist()
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        out1 = eng.generate([prompt], sp)[0]
        out2 = eng.generate([prompt], sp)[0]
        assert out1 == out2
        assert eng.alloc.hit_tokens == 0

    def test_chunked_prefill_beyond_largest_bucket(self, engine):
        """A cold prompt longer than the largest prefill bucket (64) chunks
        through it and must produce the same continuation as the same prompt
        admitted fully-cached — admission no longer depends on cache state."""
        from opsagent_tpu.serving.sampler import SamplingParams

        rng = np.random.default_rng(5)
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        prompt = rng.integers(1, 200, 100).tolist()  # > largest bucket 64
        out_cold = engine.generate([prompt], sp)[0]
        out_warm = engine.generate([prompt], sp)[0]  # now prefix-cached
        assert out_cold == out_warm

    def test_pressure_eviction_keeps_generating(self, engine):
        """Fill the pool with cached pages, then admit requests that force
        evictions; generation must stay correct (no page leaks/corruption)."""
        from opsagent_tpu.serving.sampler import SamplingParams

        rng = np.random.default_rng(2)
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        outs = {}
        for i in range(12):
            prompt = rng.integers(1, 200, 40).tolist()
            outs[i] = (prompt, engine.generate([prompt], sp)[0])
        # Re-run an early prompt (its pages may have been evicted): result
        # must be identical either way.
        prompt, expected = outs[0]
        assert engine.generate([prompt], sp)[0] == expected
