"""Speculative decoding: draft-and-verify multi-token serving (ref:
speculative sampling arXiv:2302.01318 + prompt-lookup decoding, applied
to the ZeRO-Inference weight-stream amortization of arXiv:2206.01861).

The load-bearing contract is EXACTNESS: greedy outputs must be
bit-for-bit identical with speculation on vs off across every engine
flavor (plain, prefix cache, chunked decode, split-fuse, int8, ZeRO-
Inference, TP), and temperature>0 must reproduce the target
distribution exactly (point-mass rejection sampling).  The oracle for
every identity test is the SAME engine with ``speculative`` absent.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.config import Config, SpeculativeConfig
from deepspeed_tpu.inference.kernels import PageAllocator
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.inference.speculative import (Drafter, ModelDrafter,
                                                 NgramDrafter,
                                                 build_drafter,
                                                 verify_accept)
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.topology import MeshSpec, set_current_mesh

KW = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
          prefill_bucket=8)
# a repetitive prompt (the traffic speculation exists for — the ngram
# drafter matches the motif and greedy decode loops), plus irregular
# ones that exercise rejection and the ∅-proposal path
PROMPTS = {
    "rep": ([7, 8, 9, 7, 8, 9, 7, 8], 10),
    "plain": ([5, 9, 2], 6),
    "mixed": ([17, 3, 3, 8, 1], 5),
}


@pytest.fixture(scope="module")
def gpt2_model():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=64)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def llama_model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def serve_all(eng, prompts=PROMPTS, temperature=0.0):
    for rid, (p, n) in prompts.items():
        eng.submit(rid, p, max_new_tokens=n, temperature=temperature)
    return eng.run()


# ---------------------------------------------------------------- drafter
class TestNgramDrafter:
    def test_longest_match_wins(self):
        d = NgramDrafter(max_ngram=3, min_ngram=1)
        # suffix [1,2,3] recurs at position 0; its continuation is [9,4]
        toks = [1, 2, 3, 9, 4, 1, 2, 3]
        assert d.propose(toks, 2) == [9, 4]

    def test_most_recent_earlier_occurrence(self):
        d = NgramDrafter(max_ngram=2, min_ngram=2)
        # [1,2] occurs twice before the suffix; the LATER one (followed
        # by 6) must win — recency tracks the live decode loop
        toks = [1, 2, 5, 1, 2, 6, 1, 2]
        assert d.propose(toks, 1) == [6]

    def test_falls_back_to_shorter_ngram(self):
        d = NgramDrafter(max_ngram=3, min_ngram=1)
        # no 3- or 2-gram repeat, but unigram 4 recurs → its follower
        toks = [4, 9, 1, 2, 4]
        assert d.propose(toks, 2) == [9, 1]

    def test_empty_when_nothing_matches(self):
        d = NgramDrafter(max_ngram=3, min_ngram=1)
        assert d.propose([1, 2, 3, 4, 5], 4) == []

    def test_empty_on_short_history_and_k0(self):
        d = NgramDrafter(max_ngram=3, min_ngram=2)
        assert d.propose([1, 2], 4) == []
        assert d.propose([1, 2, 1, 2], 0) == []

    def test_self_extension_fills_the_window_on_a_loop(self):
        d = NgramDrafter(max_ngram=2, min_ngram=1)
        # the match's continuation runs into the end of history; self-
        # extension re-matches on history + draft and keeps cycling the
        # period-2 loop until k tokens are drafted
        assert d.propose([3, 7, 3], 8) == [7, 3, 7, 3, 7, 3, 7, 3]

    def test_self_extension_follows_history_then_cycles(self):
        d = NgramDrafter(max_ngram=2, min_ngram=1)
        # the first match follows the history to its end ([9,1,2]),
        # then the re-match on history+draft keeps the period going
        assert d.propose([1, 2, 9, 1, 2], 6) == [9, 1, 2, 9, 1, 2]

    def test_validation(self):
        with pytest.raises(ValueError, match="min_ngram"):
            NgramDrafter(max_ngram=2, min_ngram=3)
        with pytest.raises(ValueError, match="min_ngram"):
            NgramDrafter(max_ngram=2, min_ngram=0)


class TestModelDrafter:
    def test_propose_shapes_and_determinism(self, gpt2_model, devices):
        cfg, params = gpt2_model
        d = ModelDrafter(params, cfg, draft_tokens=3, window=16)
        hist = [5, 9, 2, 7, 7, 2]
        out = d.propose(hist, 3)
        assert len(out) == 3
        assert all(isinstance(t, int) for t in out)
        assert d.propose(hist, 3) == out          # deterministic
        assert d.propose(hist, 2) == out[:2]      # k clamps
        assert d.propose(hist, 0) == []

    def test_unknown_family_rejected(self):
        with pytest.raises(TypeError, match="not a decoder family"):
            ModelDrafter({}, object(), draft_tokens=2)


# ----------------------------------------------------------------- config
class TestSpeculativeConfig:
    def test_coerce_forms(self):
        assert not SpeculativeConfig.coerce(None).enabled
        assert SpeculativeConfig.coerce(True).enabled
        assert not SpeculativeConfig.coerce(False).enabled
        sc = SpeculativeConfig.coerce({"draft_tokens": 6})
        assert sc.enabled and sc.draft_tokens == 6   # block = opt-in
        assert SpeculativeConfig.coerce(sc) is sc
        with pytest.raises(TypeError):
            SpeculativeConfig.coerce(3)

    def test_validation(self):
        with pytest.raises(ValueError, match="drafter"):
            SpeculativeConfig.from_dict({"drafter": "oracle"})
        with pytest.raises(ValueError, match="draft_tokens"):
            SpeculativeConfig.from_dict({"draft_tokens": 0})
        with pytest.raises(ValueError, match="min_ngram"):
            SpeculativeConfig.from_dict({"max_ngram": 2, "min_ngram": 5})

    def test_build_drafter_model_needs_instance(self):
        sc = SpeculativeConfig(enabled=True, drafter="model")
        with pytest.raises(ValueError, match="explicit drafter"):
            build_drafter(sc)

    def test_config_block_reaches_init_serving(self, gpt2_model, devices):
        from deepspeed_tpu.inference import init_serving

        cfg, params = gpt2_model
        c = Config.from_dict({"speculative": {"draft_tokens": 3}})
        eng = init_serving(params, cfg, config=c, **KW)
        assert eng._spec_on and eng.speculative.draft_tokens == 3
        assert isinstance(eng.drafter, NgramDrafter)

    def test_encoder_families_reject_speculation(self, devices):
        from deepspeed_tpu.models.bert import BertConfig, init_params

        cfg = BertConfig.tiny(dim=32, n_layers=1, n_heads=2)
        params = init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(NotImplementedError, match="speculative"):
            serving_engine(params, cfg, speculative=True)


# ----------------------------------------------------------- verify math
def _keys(seed=0):
    """The key operands of ``verify_accept``: a base key and a dispatch
    ordinal, split on the device into one key a (row, position)."""
    return jax.random.PRNGKey(seed), jnp.int32(0)


class TestVerifyAccept:
    def test_greedy_full_accept_and_bonus(self):
        V, K = 11, 3
        # logits whose argmax at position j is j+1 → drafts [1,2,3]
        # all accept and the bonus token is 4
        lg = np.full((1, K + 1, V), -10.0, np.float32)
        for j in range(K + 1):
            lg[0, j, j + 1] = 10.0
        drafts = np.array([[1, 2, 3]], np.int32)
        n_acc, stop = verify_accept(
            jnp.asarray(lg), jnp.asarray(drafts),
            jnp.asarray([3], jnp.int32), *_keys(),
            jnp.zeros((1,), jnp.float32))
        assert int(n_acc[0]) == 3
        assert int(stop[0, 3]) == 4

    def test_greedy_rejection_takes_target_argmax(self):
        V, K = 11, 3
        lg = np.full((1, K + 1, V), -10.0, np.float32)
        for j in range(K + 1):
            lg[0, j, j + 1] = 10.0
        # draft wrong at position 1: accept [1], correct to argmax 2
        drafts = np.array([[1, 9, 3]], np.int32)
        n_acc, stop = verify_accept(
            jnp.asarray(lg), jnp.asarray(drafts),
            jnp.asarray([3], jnp.int32), *_keys(),
            jnp.zeros((1,), jnp.float32))
        assert int(n_acc[0]) == 1
        assert int(stop[0, 1]) == 2

    def test_empty_draft_is_plain_decode_step(self):
        V, K = 7, 2
        lg = np.full((2, K + 1, V), -5.0, np.float32)
        lg[:, 0, 4] = 5.0
        n_acc, stop = verify_accept(
            jnp.asarray(lg), jnp.zeros((2, K), jnp.int32),
            jnp.zeros((2,), jnp.int32), *_keys(),
            jnp.zeros((2,), jnp.float32))
        assert np.all(np.asarray(n_acc) == 0)
        assert np.all(np.asarray(stop)[:, 0] == 4)

    def test_temperature_first_token_marginal_is_exact(self):
        """The rejection-sampling contract: the emitted first token's
        marginal equals softmax(logits/T) exactly — accept the draft d
        with probability p(d), else sample p with d's mass removed.
        Frequency check over N independent key rows."""
        N, V = 4000, 5
        logits = np.array([1.5, 0.2, -0.5, 0.8, -1.0], np.float32)
        temp = 0.7
        p = jax.nn.softmax(jnp.asarray(logits) / temp)
        d = 0                                    # the high-mass draft
        lg = np.broadcast_to(logits, (N, 2, V)).copy()
        drafts = np.full((N, 1), d, np.int32)
        n_acc, stop = verify_accept(
            jnp.asarray(lg), jnp.asarray(drafts),
            jnp.ones((N,), jnp.int32), *_keys(seed=7),
            jnp.full((N,), temp, jnp.float32))
        n_acc, stop = np.asarray(n_acc), np.asarray(stop)
        emitted = np.where(n_acc == 1, d, stop[:, 0])
        freq = np.bincount(emitted, minlength=V) / N
        # acceptance rate ≈ p(d); marginal ≈ p everywhere (±5σ)
        tol = 5 * np.sqrt(np.asarray(p) * (1 - np.asarray(p)) / N)
        assert abs(n_acc.mean() - float(p[d])) < tol[d], \
            (n_acc.mean(), float(p[d]))
        assert np.all(np.abs(freq - np.asarray(p)) < np.maximum(
            tol, 0.01)), (freq, np.asarray(p))

    def test_temperature_exhausted_draft_samples_full_target(self):
        """Rows whose drafts ran out sample the FULL target at the stop
        position — not the residual (nothing was rejected there)."""
        N, V = 4000, 4
        logits = np.array([2.0, 0.0, -1.0, 1.0], np.float32)
        p = jax.nn.softmax(jnp.asarray(logits))
        lg = np.broadcast_to(logits, (N, 2, V)).copy()
        n_acc, stop = verify_accept(
            jnp.asarray(lg), np.zeros((N, 1), np.int32),
            jnp.zeros((N,), jnp.int32), *_keys(seed=3),
            jnp.ones((N,), jnp.float32))
        freq = np.bincount(np.asarray(stop)[:, 0], minlength=V) / N
        assert np.all(np.abs(freq - np.asarray(p)) < 0.05), freq


# --------------------------------------------------------- greedy identity
class TestGreedyIdentity:
    """Speculation on vs off must be BIT-IDENTICAL for greedy across
    every engine flavor — the oracle is always the same engine without
    the speculative block."""

    def test_plain_gpt2(self, gpt2_model, devices):
        cfg, params = gpt2_model
        want = serve_all(serving_engine(params, cfg, **KW))
        got = serve_all(serving_engine(
            params, cfg, speculative={"draft_tokens": 4}, **KW))
        assert got == want

    def test_chunked_decode_baseline(self, gpt2_model, devices):
        """The spec sweep REPLACES the chunked-decode scan; its output
        must still match a decode_chunk=2 baseline exactly."""
        cfg, params = gpt2_model
        want = serve_all(serving_engine(params, cfg,
                                        decode_chunk=2, **KW))
        got = serve_all(serving_engine(
            params, cfg, decode_chunk=2,
            speculative={"draft_tokens": 3}, **KW))
        assert got == want

    def test_split_fuse(self, gpt2_model, devices):
        cfg, params = gpt2_model
        kw = dict(KW, prefill_chunk=4)
        long = {"long": (list(range(2, 21)), 6), **PROMPTS}
        want = serve_all(serving_engine(params, cfg, **kw),
                         prompts=long)
        got = serve_all(serving_engine(
            params, cfg, speculative={"draft_tokens": 3}, **kw),
            prompts=long)
        assert got == want

    def test_int8(self, gpt2_model, devices):
        cfg, params = gpt2_model
        want = serve_all(serving_engine(
            params, cfg, weight_dtype="int8", quant_group_size=16, **KW))
        got = serve_all(serving_engine(
            params, cfg, weight_dtype="int8", quant_group_size=16,
            speculative={"draft_tokens": 4}, **KW))
        assert got == want

    def test_prefix_cache(self, gpt2_model, devices):
        """Shared-prefix traffic with caching on: cache-hit admissions
        share published pages read-only, and the verify sweep's
        rollback must never disturb them (COW guard live under pc)."""
        cfg, params = gpt2_model
        prefix = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]
        prompts = {f"u{i}": (prefix + [20 + i, 30 + i], 8)
                   for i in range(4)}
        want = serve_all(serving_engine(params, cfg,
                                             prefix_cache=True, **KW),
                         prompts=prompts)
        eng = serving_engine(
            params, cfg, prefix_cache=True,
            speculative={"draft_tokens": 4}, **KW)
        got = serve_all(eng, prompts=prompts)
        assert got == want
        assert int(eng._c_pc_hits.value) > 0    # the hit path really ran

    def test_zero_inference(self, llama_model, devices):
        """THE amortization case: one verify sweep = one full layer-
        weight stream scoring K+1 positions — still token-identical to
        the resident engine, and streamed bytes per generated token
        drop with the mean acceptance length.

        Identity runs the real ngram drafter.  The byte-amortization
        assertion uses an ORACLE drafter (replays the known baseline
        output) so acceptance is perfect and the measurement isolates
        the MECHANISM — one stream per verify sweep, whatever the
        acceptance — from the draft QUALITY a random-init tiny model's
        non-repetitive continuations can't provide."""
        cfg, params = llama_model
        want = serve_all(serving_engine(params, cfg, **KW))
        zi = {"enabled": True, "tier": "host", "hbm_budget_bytes": None}
        base = serving_engine(params, cfg, zero_inference=zi, **KW)
        out_base = serve_all(base)
        assert out_base == want
        spec = serving_engine(
            params, cfg, zero_inference=zi,
            speculative={"draft_tokens": 4}, **KW)
        out_spec = serve_all(spec)
        assert out_spec == want

        class _Oracle(Drafter):
            def propose(self, tokens, k):
                t = list(tokens)
                for full in want.values():
                    if full[:len(t)] == t:
                        return full[len(t):len(t) + k]
                return []

        orac = serving_engine(
            params, cfg, zero_inference=zi, drafter=_Oracle(),
            speculative={"draft_tokens": 4}, **KW)
        assert serve_all(orac) == want
        gen = sum(len(v) - len(PROMPTS[r][0]) for r, v in want.items())
        bb = base.registry.snapshot()["counters"]
        c = orac.registry.snapshot()["counters"]
        bpt_base = bb["zi_bytes_uploaded"] / gen
        bpt_spec = c["zi_bytes_uploaded"] / gen
        mean_len = c["spec_emitted_tokens"] / c["spec_verify_slots"]
        assert mean_len > 2.0, mean_len
        # each verify sweep = ONE layer stream emitting mean_len tokens
        # per slot, vs one stream per token: decode sweeps collapse by
        # ≈ mean_len, and total streamed bytes (prefill's shared,
        # unamortized streams included) drop strictly
        assert bpt_spec < bpt_base, (bpt_spec, bpt_base)
        assert c["spec_verify_sweeps"] * 2 <= bb["serving_decode_syncs"], \
            (c["spec_verify_sweeps"], bb["serving_decode_syncs"])

    def test_tp2(self, llama_model, devices):
        cfg, params = llama_model
        want = serve_all(serving_engine(params, cfg, **KW))
        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            got = serve_all(serving_engine(
                params, cfg, mesh=mesh,
                speculative={"draft_tokens": 3}, **KW))
        finally:
            set_current_mesh(None)
        assert got == want

    def test_model_drafter(self, gpt2_model, devices):
        """A resident small-model drafter (here: the target itself over
        a short padded window — quality irrelevant, exactness not)."""
        cfg, params = gpt2_model
        want = serve_all(serving_engine(params, cfg, **KW))
        drafter = ModelDrafter(params, cfg, draft_tokens=3, window=16)
        got = serve_all(serving_engine(
            params, cfg,
            speculative={"drafter": "model", "draft_tokens": 3},
            drafter=drafter, **KW))
        assert got == want

    def test_ngram_empty_proposals_degrade_gracefully(self, gpt2_model,
                                                      devices):
        """Distinct-token prompts give the ngram drafter nothing to
        match: every sweep rides as a plain decode step (∅ proposal),
        output identical, nothing drafted until history repeats."""
        cfg, params = gpt2_model
        prompts = {"d": ([11, 23, 37, 41], 4)}
        want = serve_all(serving_engine(params, cfg, **KW),
                         prompts=prompts)
        eng = serving_engine(
            params, cfg,
            speculative={"draft_tokens": 4, "max_ngram": 4}, **KW)
        got = serve_all(eng, prompts=prompts)
        assert got == want

    def test_speculation_still_emits_under_preemption(self, gpt2_model,
                                                      devices):
        """Page pressure → vLLM-style preemption mid-speculation: the
        requeued recompute must land the same greedy tokens."""
        cfg, params = gpt2_model
        kw = dict(KW, num_pages=14, max_batch=2)
        want = serve_all(serving_engine(params, cfg, **kw))
        eng = serving_engine(
            params, cfg, speculative={"draft_tokens": 4}, **kw)
        got = serve_all(eng)
        assert got == want


# -------------------------------------------------------- rollback safety
class TestRollbackCOW:
    def test_writable_semantics(self):
        a = PageAllocator(8, cache_pages=8)
        pages = a.allocate("s1", 2)
        assert a.writable(pages[0]) and a.writable(pages[1])
        a.publish(pages[0], b"k0")
        assert not a.writable(pages[0])    # content-pinned
        a.share("s2", [pages[1]])
        assert not a.writable(pages[1])    # shared
        assert not a.writable(99)          # unowned

    def test_frontier_guard_raises_on_published_page(self, gpt2_model,
                                                     devices):
        """Manufactured violation: force-publish the frontier page of a
        live slot — the sweep must refuse to write it rather than
        silently poison the content-addressed index."""
        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, prefix_cache=True,
            speculative={"draft_tokens": 4}, **KW)
        eng.submit("x", [5, 9, 2, 7, 1, 3, 2, 8, 4], max_new_tokens=8)
        eng.step()                         # admitted + first token
        b, s = next((b, s) for b, s in enumerate(eng.slots)
                    if s is not None)
        frontier = int(eng._table_host[b, s.seq_len // eng.page_size])
        eng.allocator.publish(frontier, b"poison-test-key")
        with pytest.raises(RuntimeError, match="COW invariant"):
            eng._check_frontier_writable([(b, s)], 5)

    def test_rollback_never_mutates_published_pages(self, gpt2_model,
                                                    devices):
        """End to end: serve shared-prefix traffic with speculation,
        snapshot every published page's KV before the second wave, and
        verify the bytes are UNTOUCHED after it (rejected-draft
        garbage lands only above the frontier, never in shared
        pages)."""
        cfg, params = gpt2_model
        prefix = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]
        eng = serving_engine(
            params, cfg, prefix_cache=True,
            speculative={"draft_tokens": 4}, **KW)
        eng.submit("u0", prefix + [21, 31], max_new_tokens=8)
        eng.run()
        published = sorted(eng.allocator.key_of)
        assert published
        k_before = np.asarray(eng.cache.k[:, :, published])
        v_before = np.asarray(eng.cache.v[:, :, published])
        for i in range(1, 3):              # cache-hit waves
            eng.submit(f"u{i}", prefix + [21 + i, 31 + i],
                       max_new_tokens=8)
        eng.run()
        np.testing.assert_array_equal(
            np.asarray(eng.cache.k[:, :, published]), k_before)
        np.testing.assert_array_equal(
            np.asarray(eng.cache.v[:, :, published]), v_before)


# --------------------------------------------------- metrics + satellites
class TestTelemetryAndTrace:
    def test_spec_metric_family(self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, speculative={"draft_tokens": 4},
            telemetry=True, **KW)
        serve_all(eng)
        c = eng.registry.snapshot()["counters"]
        assert c["spec_verify_sweeps"] > 0
        assert c["spec_drafted_tokens"] >= c["spec_accepted_tokens"]
        assert c["spec_accepted_tokens"] + c["spec_rejected_tokens"] \
            == c["spec_drafted_tokens"]
        # emitted = accepted prefix + one bonus per slot-sweep
        assert c["spec_emitted_tokens"] == \
            c["spec_accepted_tokens"] + c["spec_verify_slots"]
        mean_len = c["spec_emitted_tokens"] / c["spec_verify_slots"]
        assert mean_len > 1.0, mean_len    # the repetitive prompt hits
        h = eng.registry.snapshot()["histograms"]["spec_accept_length"]
        assert h["count"] == c["spec_verify_slots"]

    def test_trace_attributes_speculation(self, gpt2_model, devices):
        from deepspeed_tpu.request_trace import request_breakdown

        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, speculative={"draft_tokens": 4},
            tracing={"sample_rate": 1.0}, **KW)
        serve_all(eng)
        events = eng.tracer.recorder.events()
        phases = {e[3] for e in events}
        assert {"spec_draft", "spec_verify", "spec_accept"} <= phases
        bd = request_breakdown(events)
        spec = bd["summary"].get("speculation")
        assert spec and spec["sweeps"] > 0
        assert spec["mean_accept_len"] > 1.0
        # per-request acceptance rides the waterfall rows
        row = bd["requests"]["rep"]
        assert row["spec_sweeps"] > 0
        assert row["spec_mean_accept_len"] >= 1.0
        # chrome export still validates (spec instants nest in spans)
        import sys, os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "tools"))
        from trace_report import breakdown_from_chrome, validate_chrome

        trace = eng.tracer.export_chrome()
        validate_chrome(trace)
        bd2 = breakdown_from_chrome(trace)
        assert bd2["summary"]["speculation"]["sweeps"] == spec["sweeps"]
        assert bd2["requests"]["rep"]["spec_sweeps"] == \
            row["spec_sweeps"]

    def test_boundary_sampling_batched(self, gpt2_model, devices):
        """Satellite: prefill-boundary tokens sample in ONE batched
        fetch per step — concurrent admissions share a sync instead of
        paying one device round-trip each.  Since ISSUE 60 the sync
        they share is the decode's own where they join it on the
        device; a speculative engine still fetches them, once."""
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, telemetry=True,
                             max_batch=4, page_size=8,
                             num_pages=32, max_seq=64,
                             prefill_bucket=8)
        for i in range(4):
            eng.submit(i, [5 + i, 9, 2], max_new_tokens=4)
        eng.step()                         # 4 admissions, no flush
        c = eng.registry.snapshot()["counters"]
        assert c["serving_boundary_syncs"] == 0
        assert c["serving_boundary_joined"] == 4
        eng.run()
        spec = serving_engine(params, cfg, telemetry=True,
                              max_batch=4, page_size=8,
                              num_pages=32, max_seq=64,
                              prefill_bucket=8,
                              speculative={"draft_tokens": 3})
        for i in range(4):
            spec.submit(i, [5 + i, 9, 2], max_new_tokens=4)
        spec.step()                        # 4 admissions, one flush
        c = spec.registry.snapshot()["counters"]
        assert c["serving_boundary_syncs"] == 1
        assert c["serving_boundary_tokens"] == 4
        assert c["serving_boundary_joined"] == 0
        assert spec.run() == eng.finished
