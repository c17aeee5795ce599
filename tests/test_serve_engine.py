"""Serving engine: scheduling semantics and the serve-while-train contract.

Determinism comes from the "steps" clock (arrivals indexed by decode
step) — no wall time anywhere.  The central claims:

  * continuous batching is a pure scheduling change: every request's
    greedy tokens are identical to the static drain-the-batch baseline
    (join/evict does not perturb surviving sequences);
  * under load it strictly wins: fewer decode steps, lower latency;
  * with a trainer publishing into a LiveParamDB mid-serve, every
    serve-side read observes a version within its group's
    ``SyncConfig.delay_for`` bound, and the shared Op history stays
    ``is_sequentially_correct`` — the paper's oracle, applied to
    inference.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.history import is_sequentially_correct
from repro.core.sync_jax import SyncConfig
from repro.models import paramlib
from repro.models.transformer import model_specs
from repro.serve import (LiveParamDB, ServeConfig, ServeEngine,
                         StaticParams, open_loop_requests)

ARCH = "llama3.2-1b"        # non-MoE: decode rows are batch-independent
SCFG = dict(batch_size=3, page_size=8, cache_len=32, clock="steps")


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config(ARCH)
    params = paramlib.init_tree(model_specs(cfg), jax.random.PRNGKey(0),
                                dtype=cfg.param_dtype)
    return cfg, params


def _requests(cfg, rate, n=10, seed=3):
    return open_loop_requests(n, rate, cfg.vocab_size, prompt_lens=(8, 16),
                              gen_lens=(2, 4, 8), seed=seed)


class TestContinuousVsStatic:
    def test_token_identical_and_wins_under_load(self, model):
        cfg, params = model
        reqs = _requests(cfg, rate=100.0)    # near-simultaneous arrivals
        reports = {}
        for cont in (True, False):
            scfg = ServeConfig(continuous=cont, **SCFG)
            reports[cont] = ServeEngine(cfg, params, scfg).run(reqs)
        assert reports[True].outputs == reports[False].outputs
        assert reports[True].n_requests == len(reqs)
        for rep in reports.values():
            for r in reqs:
                assert len(rep.outputs[r.rid]) == r.gen_len
        # continuous strictly wins when the batch is contended
        assert reports[True].decode_steps < reports[False].decode_steps
        assert reports[True].latency_p50 < reports[False].latency_p50
        assert reports[True].utilization > reports[False].utilization

    def test_token_identical_with_staggered_arrivals(self, model):
        """Sparse arrivals: sequences join/evict mid-decode at many
        different interleavings; tokens still match the static oracle."""
        cfg, params = model
        reqs = _requests(cfg, rate=0.5, seed=5)
        outs = {}
        for cont in (True, False):
            scfg = ServeConfig(continuous=cont, **SCFG)
            outs[cont] = ServeEngine(cfg, params, scfg).run(reqs).outputs
        assert outs[True] == outs[False]

    def test_raw_param_tree_is_wrapped(self, model):
        cfg, params = model
        eng = ServeEngine(cfg, params, ServeConfig(**SCFG))
        assert isinstance(eng.db, StaticParams)
        assert eng.db.get() is params


class TestServeWhileTrain:
    def test_delay_bounds_on_every_read(self, model):
        """A trainer publishes every 3 decode steps; serve-side reads of
        each delay group must stay within delay_for, non-vacuously (some
        reads actually observe stale versions), and the combined Op
        history must satisfy the Theorem-5 per-partition conditions."""
        cfg, params = model
        sync = SyncConfig(delta=4, group_delays=(("groups", 4),
                                                 ("embed", 1)))
        db = LiveParamDB(params, sync)
        eng = ServeEngine(cfg, db, ServeConfig(**SCFG))
        itr = [0]

        def trainer(step):
            if step % 3 == 0:
                itr[0] += 1
                # a real weight change, so stale reads serve old values
                new = jax.tree.map(lambda x: x * 0.999, params)
                db.publish(new, itr[0])

        rep = eng.run(_requests(cfg, rate=0.5), step_hook=trainer)
        assert rep.n_requests == 10 and itr[0] > 2
        assert len(db.read_log) > 0
        for r in db.read_log:
            assert 0 <= r.staleness <= r.delay
        assert any(r.staleness > 0 for r in db.read_log)
        # both delay groups were exercised
        assert {r.delay for r in db.read_log} == {1, 4}
        assert is_sequentially_correct(db.telemetry.history, db.n_chunks)
        stats = db.telemetry.summary()
        assert stats["stale_reads"] > 0
        assert stats["max_staleness"] <= 4

    def test_publish_out_of_order_rejected(self, model):
        cfg, params = model
        db = LiveParamDB(params, SyncConfig(delta=2))
        db.publish(params, 1)
        with pytest.raises(ValueError, match="out of order"):
            db.publish(params, 3)

    def test_fresh_groups_follow_the_head(self, model):
        """delay 0 groups must re-read every publish (exact RC)."""
        cfg, params = model
        db = LiveParamDB(params, SyncConfig(delta=0))
        for i in range(1, 4):
            new = jax.tree.map(lambda x: x * (1.0 - 0.1 * i), params)
            db.publish(new, i)
            got = db.get()
            leaf = jax.tree_util.tree_leaves(got)[0]
            want = jax.tree_util.tree_leaves(new)[0]
            assert jnp.array_equal(leaf, want)
        assert all(r.staleness == 0 for r in db.read_log)
        assert is_sequentially_correct(db.telemetry.history, db.n_chunks)


def _shared_reqs(cfg, n=10, rate=100.0, seed=3, n_prefixes=3):
    from repro.serve import shared_prefix_requests
    return shared_prefix_requests(n, rate, cfg.vocab_size,
                                  n_prefixes=n_prefixes, prefix_len=16,
                                  suffix_lens=(4, 8), gen_lens=(2, 4, 8),
                                  seed=seed)


class TestChunkedPrefillAndPrefixCache:
    def test_chunked_matches_static_oracle(self, model):
        """Chunked prefill is a pure scheduling change: tokens identical
        to the whole-prompt static baseline on every request."""
        cfg, params = model
        reqs = _shared_reqs(cfg)
        static = ServeEngine(cfg, params, ServeConfig(
            continuous=False, **SCFG)).run(reqs)
        chunked = ServeEngine(cfg, params, ServeConfig(
            continuous=True, prefill_chunk=8, **SCFG)).run(reqs)
        assert chunked.outputs == static.outputs
        assert chunked.prefill_chunks > 0

    def test_prefix_cache_matches_oracle_and_saves_chunks(self, model):
        """Prefix adoption changes *where* K/V come from, never the
        tokens; shared-prefix traffic must hit and skip prefill work."""
        cfg, params = model
        reqs = _shared_reqs(cfg)
        static = ServeEngine(cfg, params, ServeConfig(
            continuous=False, **SCFG)).run(reqs)
        nocache = ServeEngine(cfg, params, ServeConfig(
            continuous=True, prefill_chunk=8, **SCFG)).run(reqs)
        cached = ServeEngine(cfg, params, ServeConfig(
            continuous=True, prefix_cache=True, **SCFG)).run(reqs)
        assert cached.outputs == static.outputs
        assert cached.prefix_hit_rate > 0.3
        assert cached.prefill_chunks < nocache.prefill_chunks
        assert cached.ttft_p50 <= nocache.ttft_p50

    def test_prefix_cache_staggered_arrivals(self, model):
        """Sparse arrivals: adoption, COW wraps and trie churn interleave
        with decode at many offsets; tokens still match the oracle."""
        cfg, params = model
        reqs = _shared_reqs(cfg, rate=0.5, seed=5)
        static = ServeEngine(cfg, params, ServeConfig(
            continuous=False, **SCFG)).run(reqs)
        cached = ServeEngine(cfg, params, ServeConfig(
            continuous=True, prefix_cache=True, **SCFG)).run(reqs)
        assert cached.outputs == static.outputs

    def test_eviction_under_pressure_stays_correct(self, model):
        """Minimal pool headroom + more hot prefixes than it can hold:
        the trie must evict (not crash) and tokens must stay exact."""
        cfg, params = model
        reqs = _shared_reqs(cfg, n=12, n_prefixes=6, seed=9)
        static = ServeEngine(cfg, params, ServeConfig(
            continuous=False, **SCFG)).run(reqs)
        cached = ServeEngine(cfg, params, ServeConfig(
            continuous=True, prefix_cache=True, prefix_seqs=1,
            **SCFG)).run(reqs)
        assert cached.outputs == static.outputs

    def test_report_fields(self, model):
        cfg, params = model
        rep = ServeEngine(cfg, params, ServeConfig(
            continuous=True, prefix_cache=True, **SCFG)).run(
                _shared_reqs(cfg, n=6))
        assert rep.ttft_p50 >= 0 and rep.ttft_p99 >= rep.ttft_p50
        assert rep.prefill_chunks >= 0
        assert 0.0 <= rep.prefix_hit_rate <= 1.0
        for f in [rep.outputs[r] for r in rep.outputs]:
            assert len(f) > 0

    def test_config_validation(self):
        with pytest.raises(ValueError, match="continuous"):
            ServeConfig(continuous=False, prefill_chunk=8, **SCFG)
        with pytest.raises(ValueError, match="top_p"):
            ServeConfig(top_p=0.0, **SCFG)
        # prefix_cache implies a page-sized prefill chunk
        scfg = ServeConfig(prefix_cache=True, **SCFG)
        assert scfg.prefill_chunk == SCFG["page_size"]


class TestSampling:
    def test_deterministic_across_schedules(self, model):
        """Sampling is keyed by (request, token index) only: continuous
        + prefix-cached and static schedules draw identical tokens."""
        cfg, params = model
        reqs = _shared_reqs(cfg)
        kw = dict(temperature=0.8, top_p=0.9, sample_seed=7)
        a = ServeEngine(cfg, params, ServeConfig(
            continuous=True, prefix_cache=True, **kw, **SCFG)).run(reqs)
        b = ServeEngine(cfg, params, ServeConfig(
            continuous=False, **kw, **SCFG)).run(reqs)
        greedy = ServeEngine(cfg, params, ServeConfig(
            continuous=False, **SCFG)).run(reqs)
        assert a.outputs == b.outputs
        assert a.outputs != greedy.outputs       # it actually sampled
        c = ServeEngine(cfg, params, ServeConfig(
            continuous=False, temperature=0.8, top_p=0.9, sample_seed=8,
            **SCFG)).run(reqs)
        assert c.outputs != b.outputs            # seed matters


def _req(rid, arrival, prompt_len, gen_len):
    from repro.serve.workload import Request
    return Request(rid=rid, arrival=arrival,
                   prompt=tuple((7 * rid + i) % 97 + 1
                                for i in range(prompt_len)),
                   gen_len=gen_len)


# A chunked prompt (20 tokens: chunks at 0, 8 and 12 of 8) and a short one
# (5 tokens, under the chunk) due at once, then a short one after an idle
# gap.  By hand, under the steps clock: A is planned at tick 0, chunked at
# ticks 0-2 and takes its first token at 3; B is planned at tick 3 (behind
# A in the prefill queue), gets no chunk, and feeds its 5 prompt tokens one
# a tick through the decode steps of ticks 4-8, so its first token comes at
# 9 = 3 + 1 + 5; it finishes at 10.  The engine then waits for C (12.5),
# planned at 12.5 and first at 18.5.  Working iterations: ticks 0-9 and
# 7 for C; decode steps: ticks 3-9 and C's 6.
TRACE_REQS = [(0, 0.0, 20, 2), (1, 0.0, 5, 2), (2, 12.5, 5, 2)]
CHUNKED = dict(SCFG, prefill_chunk=8)


class TestTracing:
    def test_prompt_counters_by_hand(self, model):
        cfg, params = model
        rep = ServeEngine(cfg, params, ServeConfig(**CHUNKED)).run(
            [_req(*r) for r in TRACE_REQS])
        got = {f.rid: (f.t_start, f.t_first, f.tail_tokens)
               for f in rep.requests}
        assert got == {0: (0.0, 3.0, 0), 1: (3.0, 9.0, 5),
                       2: (12.5, 18.5, 5)}
        assert rep.prefill_chunks == 3 and rep.decode_steps == 13
        assert sorted(f.rid for f in rep.requests) == [0, 1, 2]
        assert rep.t0 > 0

    def test_short_prompt_takes_the_tail(self, model):
        """Under the chunk: no chunk, every prompt token one a tick, and
        the first token one tick after the last prompt token's."""
        cfg, params = model
        rep = ServeEngine(cfg, params, ServeConfig(**CHUNKED)).run(
            [_req(0, 0.0, 5, 3)])
        (f,) = rep.requests
        assert f.tail_tokens == 5 and f.t_first - f.t_start == 1 + 5
        assert rep.prefill_chunks == 0 and len(f.tokens) == 3

    def test_whole_prompt_starts_at_admission(self, model):
        """One slot: B waits for A to retire (tick 2), and its prompt work
        starts at its admission there, not at its arrival."""
        cfg, params = model
        rep = ServeEngine(cfg, params, ServeConfig(
            **dict(SCFG, batch_size=1))).run(
                [_req(0, 0.0, 6, 3), _req(1, 0.0, 6, 2)])
        got = {f.rid: (f.arrival, f.t_start, f.t_first, f.tail_tokens)
               for f in rep.requests}
        assert got == {0: (0.0, 0.0, 0.0, 0), 1: (0.0, 2.0, 2.0, 0)}

    def test_spans_in_a_profiler_trace(self, model, tmp_path):
        """One tick span per working iteration; every other engine span
        inside a tick, but the wait; the step hook outside every tick."""
        from jax.profiler import ProfileData
        cfg, params = model
        eng = ServeEngine(cfg, params, ServeConfig(**CHUNKED))
        reqs = [_req(*r) for r in TRACE_REQS]
        eng._warmup(reqs)

        def hook(step):
            with jax.profiler.TraceAnnotation("test.hook"):
                pass

        with jax.profiler.trace(str(tmp_path)):
            rep = eng.run(reqs, step_hook=hook)
        (pb,) = tmp_path.rglob("*.xplane.pb")
        ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in ProfileData.from_file(str(pb)).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events
              if e.name.startswith(("repro.serve.", "test.hook"))]
        by = {}
        for name, a, b in ev:
            by.setdefault(name, []).append((a, b))
        ticks = by["repro.serve.tick"]
        assert len(ticks) == 10 + 7
        assert len(by["repro.serve.wait"]) == 1
        assert len(by["repro.serve.admit"]) == len(ticks)
        assert len(by["repro.serve.readback"]) == rep.decode_steps == 13
        assert len(by["test.hook"]) == rep.decode_steps

        def inside(a, b):
            return any(x <= a and b <= y for x, y in ticks)

        for name in ("admit", "prefill", "decode", "readback", "update"):
            spans = by[f"repro.serve.{name}"]
            assert spans and all(inside(a, b) for a, b in spans), name
        for a, b in by["repro.serve.wait"] + by["test.hook"]:
            assert all(b <= x or a >= y for x, y in ticks)
