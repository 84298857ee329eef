"""Async serving core: submit/step/drain, batched insert, dedup, sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.compressed import kernel_backend
from repro.models import api
from repro.olap import operators as OPS
from repro.olap.table import Table
from repro.serving.engine import Engine
from repro.serving.sampler import SamplingConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="ta", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=260,
                      max_seq=256)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestAsyncCore:
    def test_interleaved_submit_during_decode(self, tiny):
        """submit() mid-flight lands in a free slot and matches the
        output of a fresh all-at-once run (greedy is deterministic)."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        r1 = eng.submit("alpha", max_new=6)
        r2 = eng.submit("beta", max_new=6)
        eng.step()                      # both admitted, decode in flight
        assert not r1.done and not r2.done
        r3 = eng.submit("gamma", max_new=6)     # streams in mid-decode
        eng.drain()
        assert all(r.done for r in (r1, r2, r3))
        ref = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        assert ref.generate(["alpha", "beta", "gamma"], max_new=6) \
            == [r1.text, r2.text, r3.text]

    def test_follower_attaches_to_inflight_leader(self, tiny):
        """A duplicate of a request that is ALREADY decoding rides on it:
        no second prefill, no slot, identical output."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=1, max_len=64, buckets=(16,))
        r1 = eng.submit("twin prompt", max_new=6)
        eng.step()                      # r1 now active in the only slot
        assert not r1.done
        r2 = eng.submit("twin prompt", max_new=6)
        eng.drain()
        assert r2.done and r2.text == r1.text
        assert eng.stats.prefills == 1
        assert eng.stats.cache_hits == 1

    def test_batched_admission_single_insert_call(self, tiny):
        """An N-row admission batch scatters into slots with exactly one
        jitted insert call (no per-row scatter loop)."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=4, max_len=64, buckets=(16,),
                     use_result_cache=False)
        calls = []
        orig = eng._insert

        def counting_insert(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        eng._insert = counting_insert
        outs = eng.generate(["a1", "b22", "c333", "d4444"], max_new=3)
        assert len(outs) == 4
        assert len(calls) == 1

    def test_drain_empty_engine_is_noop(self, tiny):
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,))
        assert eng.drain() == []
        assert eng.step() == []


class TestSampling:
    def test_temperature_zero_bitwise_matches_greedy_default(self, tiny):
        """Explicit temperature=0 config lowers to the same greedy decode
        as the default engine — bitwise-identical outputs."""
        cfg, params = tiny
        texts = ["check me", "and me too", "third row"]
        base = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                      use_result_cache=False)
        t0 = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                    use_result_cache=False,
                    sampling=SamplingConfig(temperature=0.0, seed=123))
        assert base.generate(texts, max_new=8) == t0.generate(texts,
                                                              max_new=8)

    def test_greedy_matches_reference_decode(self, tiny):
        """Slot-vmapped sampled decode (temp=0) == direct api greedy."""
        from repro.core.policy import greedy_decode
        from repro.training import data as D
        cfg, params = tiny
        tok = D.ByteTokenizer(260)
        text = "check me"
        ids = tok.encode(text, bos=True) + [tok.SEP]
        toks = np.zeros((1, 16), np.int32)
        toks[0, :len(ids)] = ids
        ref = greedy_decode(params, cfg, jnp.asarray(toks), 6,
                            lengths=jnp.asarray([len(ids)]))
        eng = Engine(params, cfg, slots=1, max_len=64, buckets=(16,),
                     use_result_cache=False,
                     sampling=SamplingConfig(temperature=0.0))
        out = eng.generate([text], max_new=6)[0]
        want = tok.decode([t for t in np.asarray(ref)[0] if t != tok.EOS])
        assert out == want

    def test_admission_waves_sample_independently(self, tiny):
        """Regression: successive admission waves must not reuse one
        PRNG key (identical prompts drew identical first tokens)."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False,
                     sampling=SamplingConfig(temperature=1.5, seed=0))
        reqs = [eng.submit("same prompt", max_new=2) for _ in range(8)]
        eng.drain()                     # 4 admission waves of 2 slots
        waves = [tuple(r.out_ids[0] for r in reqs[i:i + 2])
                 for i in range(0, 8, 2)]
        assert len(set(waves)) > 1

    def test_max_new_budget_exact(self, tiny):
        """Regression: max_new=1 must yield exactly one token (the
        prefill-sampled token), not burn a decode step for a second."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        r = eng.submit("hello", max_new=1)
        eng.drain()
        assert r.done and len(r.out_ids) == 1
        assert eng.stats.decode_steps == 0

    def test_eos_at_prefill_retires_without_decoding(self, tiny):
        """Regression: a first (prefill-sampled) token == EOS must end
        the row — no slot occupancy, no post-EOS junk in the text."""
        from repro.serving import engine as E
        cfg, params = tiny
        eng = Engine(params, cfg, slots=1, max_len=64, buckets=(16,),
                     use_result_cache=False)
        # force the admission sample to EOS regardless of the model
        orig = E.sample
        E.sample = lambda logits, key, **kw: jnp.full(
            logits.shape[:-1], eng.tok.EOS, jnp.int32)
        try:
            r = eng.submit("ends at once", max_new=8)
            eng.drain()
        finally:
            E.sample = orig
        assert r.done and r.text == ""
        assert r.out_ids == [eng.tok.EOS]
        assert eng.stats.decode_steps == 0

    def test_sampled_decode_deterministic_per_seed(self, tiny):
        cfg, params = tiny
        mk = lambda s: Engine(params, cfg, slots=2, max_len=64,
                              buckets=(16,), use_result_cache=False,
                              sampling=SamplingConfig(temperature=0.9,
                                                      top_k=8, seed=s))
        texts = ["sample a", "sample b"]
        assert mk(7).generate(texts, max_new=6) \
            == mk(7).generate(texts, max_new=6)


class TestBucketsAndStats:
    def test_bucket_ladder_never_empty(self, tiny):
        cfg, params = tiny
        eng = Engine(params, cfg, slots=1, max_len=32, buckets=(64, 128))
        assert eng.buckets and max(eng.buckets) < 32
        assert len(eng.generate(["hello"], max_new=2)) == 1

    def test_long_prompt_truncation_surfaced(self, tiny):
        cfg, params = tiny
        eng = Engine(params, cfg, slots=1, max_len=32, buckets=(16,),
                     use_result_cache=False)
        req = eng.submit("z" * 200, max_new=2)
        eng.drain()
        assert req.truncated
        assert eng.stats.truncated == 1

    def test_cache_accounting_consistent(self, tiny):
        """Regression: follower dedup counts exactly ONE hit (the old
        path recorded a miss in get() then manually bumped hits)."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,))
        eng.generate(["same", "same", "same"], max_new=4)
        rc = eng.result_cache
        assert (rc.hits, rc.misses) == (2, 1)
        assert eng.stats.cache_hits == rc.hits
        assert abs(rc.hit_rate - 2 / 3) < 1e-9
        eng.generate(["same"], max_new=4)        # stored-result hit
        assert (rc.hits, rc.misses) == (3, 1)
        assert eng.stats.cache_hits == rc.hits

    def test_slot_utilization_tracked(self, tiny):
        cfg, params = tiny
        eng = Engine(params, cfg, slots=4, max_len=64, buckets=(16,),
                     use_result_cache=False)
        eng.generate(["only one row"], max_new=4)
        assert 0.0 < eng.stats.slot_utilization <= 0.25 + 1e-9


class TestStreamingOperators:
    def test_llm_join_residency_bounded_by_chunk(self, tiny):
        """O(n·k) join candidates stream through the engine: peak
        resident requests track the chunk bound, not the pair count."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(32,),
                     use_result_cache=False)
        n = 12
        left = Table({"name": [f"acme{i}" for i in range(n)]})
        right = Table({"name": [f"acme{i}x" for i in range(n)]})
        chunk = 4
        OPS.llm_join(left, right, ("name", "name"), eng, max_new=2,
                     chunk=chunk)
        pairs = n * n          # single block: every left x every right
        assert eng.stats.rows == pairs
        assert eng.stats.peak_inflight <= chunk + eng.slots
        assert eng.stats.peak_inflight < pairs

    def test_streamed_map_matches_generate(self, tiny):
        cfg, params = tiny
        vals = [f"row {i}" for i in range(9)]
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(32,),
                     use_result_cache=False)
        t = OPS.llm_map(Table({"c": vals}), "c", eng, prompt="sum: ",
                        out_col="o", max_new=4, chunk=3)
        ref = Engine(params, cfg, slots=2, max_len=64, buckets=(32,),
                     use_result_cache=False)
        assert t["o"] == ref.generate(["sum: " + v for v in vals],
                                      max_new=4)

    def test_generator_prompts_freed_after_completion(self, tiny):
        """Finished requests drop their prompt ids (residency bound)."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        outs = OPS._invoke(eng, (f"p{i}" for i in range(6)), max_new=2,
                           chunk=2)
        assert len(outs) == 6 and all(isinstance(o, str) for o in outs)

    def test_stream_throttle_ignores_foreign_completions(self, tiny):
        """Regression: requests submitted outside generate_stream must
        not loosen its chunk bound when they finish mid-stream."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        foreign = eng.submit("foreign row", max_new=2)
        outs = eng.generate_stream((f"s{i}" for i in range(6)), max_new=2,
                                   chunk=2)
        assert foreign.done                     # drained alongside
        assert len(outs) == 6
        # bound: chunk of this call + slots + the one foreign request
        assert eng.stats.peak_inflight <= 2 + eng.slots + 1
        ref = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        assert outs == ref.generate([f"s{i}" for i in range(6)], max_new=2)

    def test_stream_throttle_skips_followers(self, tiny):
        """Regression: followers (deduped duplicates, no prompt/slot)
        must not stall admission of later distinct prompts — A and B
        decode concurrently even with duplicates of A in between."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,))
        prompts = ["aaa", "aaa", "aaa", "aaa", "bbb"]
        outs = eng.generate_stream(iter(prompts), max_new=6, chunk=2)
        assert len(outs) == 5 and outs[0] == outs[1] == outs[2] == outs[3]
        # A and B were admitted into slots together: some decode steps
        # ran 2 busy slots (with the stall bug, A always decoded alone)
        assert eng.stats.busy_slot_steps > eng.stats.decode_steps


def serial_loop(eng, texts, max_news, *, prefix=None):
    """The decode loop with no step in flight, the reference the
    engine's loop must match: each step launched from tokens the host
    has pulled, and pulled in the tick that launched it.  It shares the
    engine's admission and programs, so the two differ in the loop
    alone."""
    reqs = [eng.submit(t, max_new=m, prefix=prefix)
            for t, m in zip(texts, max_news)]
    eng._init_slots()
    tok = np.zeros(eng.slots, np.int32)
    with kernel_backend(eng.backend):
        while len(eng.batcher) or eng._active:
            before = set(eng._active)
            free = [s for s in range(eng.slots) if s not in eng._active]
            take = eng.batcher.take(len(free))
            if take:
                eng._admit(take, free)
            for s in set(eng._active) - before:
                tok[s] = eng._active[s].out_ids[0]
            if not eng._active:
                continue
            tables = (eng._tables(),) if eng._paged else ()
            nxt, conf, eng._slot_state = eng._decode(
                eng.params, eng._slot_state, *tables, jnp.asarray(tok),
                jnp.asarray(eng._cur_pos), jnp.int32(eng._decode_ctr))
            eng._decode_ctr += 1
            nxt, conf = np.asarray(nxt), np.asarray(conf)
            for s, r in list(eng._active.items()):
                t = int(nxt[s])
                r.out_ids.append(t)
                r.confidence = min(r.confidence, float(conf[s]))
                tok[s] = t
                eng._cur_pos[s] += 1
                if t == eng.tok.EOS or len(r.out_ids) >= r.max_new \
                        or eng._cur_pos[s] >= eng.max_len - 1:
                    del eng._active[s]
                    eng._release_slot(s)
                    eng._retire(r)
    return reqs


def _rows(reqs):
    return [(r.out_ids, r.text, r.confidence) for r in reqs]


class TestOneStepInFlight:
    """The engine launches step t+1 from step t's tokens on the device
    and pulls step t's tokens while step t+1 runs."""

    TPL = "fix this value: "
    TEXTS = [f"row {i} {'xy' * (i % 4)}" for i in range(10)]
    # max_new 1 ends at admission, 2 after one step, 16 runs long: with
    # 3 slots, admissions land in slots that max_new freed that tick
    MAX_NEW = [(1, 2, 16)[i % 3] for i in range(10)]

    @pytest.mark.parametrize("layout", ["paged", "contiguous"])
    @pytest.mark.parametrize("sampling", [
        None, SamplingConfig(temperature=0.9, top_k=8, seed=5)],
        ids=["greedy", "sampled"])
    def test_same_rows_as_the_serial_loop(self, tiny, layout, sampling):
        cfg, params = tiny
        mk = lambda: Engine(params, cfg, slots=3, max_len=64,
                            buckets=(16, 32), use_result_cache=False,
                            kv_layout=layout, sampling=sampling)
        eng, ref = mk(), mk()
        assert eng._paged == (layout == "paged")
        got = [eng.submit(self.TPL + t, max_new=m, prefix=self.TPL)
               for t, m in zip(self.TEXTS, self.MAX_NEW)]
        eng.drain()
        want = serial_loop(ref, [self.TPL + t for t in self.TEXTS],
                           self.MAX_NEW, prefix=self.TPL)
        assert _rows(got) == _rows(want)
        assert [len(r.out_ids) for r in got] == self.MAX_NEW
        assert eng.stats.prefix_hits == ref.stats.prefix_hits > 0
        assert eng._decode_ctr == ref._decode_ctr == eng.stats.decode_steps
        # a slot-step per decoded token past the first, nothing more
        assert eng.stats.busy_slot_steps == sum(m - 1 for m in self.MAX_NEW)

    def test_rows_match_reference_greedy_decode(self, tiny):
        """More rows than slots, 16 new tokens each, against the model's
        own greedy decode of each row alone."""
        from repro.core.policy import greedy_decode
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        texts = ["check me", "and me", "third", "fourth row"]
        outs = eng.generate(texts, max_new=16)
        for text, out in zip(texts, outs):
            ids = eng.tok.encode(text, bos=True) + [eng.tok.SEP]
            toks = np.zeros((1, 16), np.int32)
            toks[0, :len(ids)] = ids
            ref = greedy_decode(params, cfg, jnp.asarray(toks), 16,
                                lengths=jnp.asarray([len(ids)]))
            assert out == eng.tok.decode(
                [t for t in np.asarray(ref)[0] if t != eng.tok.EOS])

    def test_rows_return_when_their_last_token_is_pulled(self, tiny):
        """A row ending with step t frees its slot in the tick that
        launched step t and comes back a tick later, from a tick that
        launches nothing when no row is left to decode."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        a, b = (eng.submit(t, max_new=2) for t in ("first", "second"))
        assert eng.step() == []            # admitted, step 0 in flight
        assert not eng._active and eng.has_work()
        assert not a.done and len(a.out_ids) == 1
        handle = eng.step_begin()           # nothing left to decode
        assert handle.nxt is None
        assert eng.step_finish(handle) == [a, b]
        assert [len(r.out_ids) for r in (a, b)] == [2, 2]
        assert not eng.has_work() and eng.step() == []
        assert eng.stats.decode_steps == 1
        assert eng.stats.host_syncs == 2 * 1 + 2 * eng.stats.prefills

    def test_pipelined_steps_count_steps_launched_before_a_pull(self, tiny):
        """Every step is launched with the one before it unpulled, but
        the first after the engine emptied."""
        cfg, params = tiny
        eng = Engine(params, cfg, slots=2, max_len=64, buckets=(16,),
                     use_result_cache=False)
        eng.generate([f"row {i}" for i in range(5)], max_new=4)
        st = eng.stats
        assert st.decode_steps >= 6
        assert st.pipelined_steps == st.decode_steps - 1
        eng.generate(["again", "and again"], max_new=3)
        assert st.pipelined_steps == st.decode_steps - 2

    def test_eos_during_decode_is_seen_one_step_late(self, tiny):
        """Slot 0 emits EOS at the engine's third decode step.  The row
        ends there with nothing after EOS, the token decoded past it is
        dropped (one extra busy slot-step), the slot takes the next
        queued row, and drain returns every row."""
        from repro.serving import engine as E
        cfg, params = tiny
        mk = lambda: Engine(params, cfg, slots=2, max_len=64,
                            buckets=(16,), use_result_cache=False)
        eng = mk()
        texts = ["ends early", "runs on", "third row", "fourth row"]
        max_new = [8, 8, 4, 4]
        at = jax.random.fold_in(eng._key, 2)
        orig = E.sample

        def forced(logits, key, **kw):
            tok = orig(logits, key, **kw)
            if key is None:                 # greedy admission sample
                return tok
            hit = jnp.all(key == at) & (jnp.arange(tok.shape[0]) == 0)
            return jnp.where(hit, eng.tok.EOS, tok)

        E.sample = forced
        try:
            reqs = [eng.submit(t, max_new=m)
                    for t, m in zip(texts, max_new)]
            done, slot_of = [], {}
            while eng.has_work():
                handle = eng.step_begin()
                for s, r in eng._active.items():
                    slot_of.setdefault(r.rid, s)
                done += eng.step_finish(handle)
        finally:
            E.sample = orig
        early = reqs[0]
        assert early.out_ids[-1] == eng.tok.EOS and len(early.out_ids) == 4
        assert early.text == eng.tok.decode(early.out_ids[:-1])
        assert slot_of[early.rid] == 0 and slot_of[reqs[2].rid] == 0
        assert sorted(r.rid for r in done) == [r.rid for r in reqs]
        assert [len(r.out_ids) for r in reqs[1:]] == max_new[1:]
        assert eng.stats.busy_slot_steps == 1 + sum(
            len(r.out_ids) - 1 for r in reqs)
        # the row beside it decodes as it would with no EOS at all
        assert reqs[1].text == mk().generate(texts[:2], max_new=8)[1]
