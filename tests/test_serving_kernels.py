"""Which kernel reads the cache: the rule of the build (``kernels.
paged_reader``), the readers it chooses between and the sampler every
engine runs (ref: DeepSpeed-FastGen's kernel injection — the serving
engine picks kernels ONCE at build, never at trace time).

Oracles:
  * the XLA gather — the Mosaic readers, in interpret mode on the CPU,
    match it at the shapes the cells send
    (``test_serving_kernels_interpret*.py``);
  * ``dequantize_pages`` — the int8-resident read is the gather over
    the dequantized pool, bit for bit;
  * ``np.argmax`` and ``jax.random.categorical`` — ``_sample_rows``;
  * ``resolve_serving_kernels`` — resolved once, from what the build can
    observe; no config block and no environment variable reaches it,
    and what ``/statusz`` reports is what the compiled programs baked.
"""

import importlib.util
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.config import Config, KVTierConfig
from deepspeed_tpu.inference import init_inference, init_serving
from deepspeed_tpu.inference.kernels import (
    ServingKernelPolicy, dequantize_pages, latent_reader,
    paged_attention_reference, paged_attention_step,
    paged_chunk_attention_reference, paged_reader, quantize_kv_rows,
    resolve_serving_kernels)
from deepspeed_tpu.inference.kv_tier import KV_TIER_QUANT_RTOL, quantize_page
from deepspeed_tpu.inference.serving import _sample_rows, serving_engine
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.models.family import CacheRow


@pytest.fixture(scope="module")
def gpt2_model():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def llama_model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------------------------------------------------------- config
class TestQuantizedResidentConfig:
    def test_quantized_resident_requires_quantize_cold(self):
        with pytest.raises(ValueError, match="quantize_cold"):
            KVTierConfig.coerce({"quantized_resident": True,
                                 "quantize_cold": False})
        k = KVTierConfig.coerce({"quantized_resident": True,
                                 "quantize_cold": True})
        assert k.quantized_resident


# ----------------------------------------------------------- resolution
class TestResolveServingKernels:
    def test_defaults(self):
        """Off the chip (``interpret``) every reader is the gather; the
        policy is a report with six rows and nothing to request."""
        p = resolve_serving_kernels(interpret=True)
        assert p._fields == ("decode", "chunk", "window", "state_step",
                             "fallbacks", "state_chunk", "experts")
        assert p.experts == ("none", "no expert layer")
        assert p.decode == p.chunk == ("xla", "interpret: no TPU backend")
        assert (p.state_step, p.fallbacks) == ("xla", ())
        assert p.state_chunk == (
            "xla", "the family states no block of its rule")
        # what a directly constructed engine reports: all gathers
        assert ServingKernelPolicy().decode[0] == "xla"

    def test_as_dict_shape(self):
        d = resolve_serving_kernels(tp=True, recurrent=True).as_dict()
        assert sorted(d) == ["chunk", "decode", "experts", "fallbacks",
                             "state_chunk", "state_step", "window"]
        assert d["experts"] == {"product": "none",
                                "reason": "no expert layer"}
        assert d["state_chunk"]["reader"] == "xla"
        assert d["decode"] == {
            "reader": "xla", "write": "scatter",
            "reason": "tp: KV heads are sharded over the mesh"}
        assert d["fallbacks"] == [{
            "field": "state_step=pallas", "demoted_to": "xla",
            "reason": "tp: the kernel is one device's"}]
        # a mesh takes nothing from a family that steps no state
        assert resolve_serving_kernels(tp=True).fallbacks == ()


    @pytest.mark.parametrize("build,write,why", [
        ({}, "kernel", "decode on one device over float pages"),
        ({"tp": True}, "scatter", "tp: KV heads are sharded"),
        ({"quantized_resident": True}, "scatter", "int8-resident pages"),
        ({"interpret": True}, "scatter", "interpret: no TPU backend"),
    ], ids=["one_device", "tp", "quantized_resident", "cpu"])
    def test_the_build_says_where_a_decode_steps_row_is_written(
            self, build, write, why):
        """``decode.write`` follows the decode reader and nothing else:
        the Mosaic reader writes the step's K/V row itself ("kernel");
        where the gather stays, so does the row scatter before it, for
        the reader's own reason."""
        policy = resolve_serving_kernels(**build)
        d = policy.as_dict()["decode"]
        assert sorted(d) == ["reader", "reason", "write"]
        assert d["write"] == write and why in d["reason"]
        assert (d["write"] == "kernel") == (
            d["reader"] == "dstpu_paged_decode")
        # a latent family's reader has a writer of its own before it
        latent = policy._replace(decode=latent_reader(policy.decode))
        assert latent.as_dict()["decode"]["write"] == "scatter"


# ---------------------------------------------------------- int8 codec
class TestQuantCodecParity:
    """quantize_kv_rows (device, jnp) and kv_tier.quantize_page (host,
    np) must agree bit-for-bit — quantized_resident round-trips pages
    between them (demote fetches device codes verbatim, promote
    publishes host codes verbatim)."""

    def test_bit_exact_parity(self):
        rng = np.random.default_rng(0)
        x = (3.0 * rng.standard_normal((2, 5, 8, 16))).astype(np.float32)
        x[0, 1, 2] = 0.0                     # a zero row: scale 1.0
        cj, sj = quantize_kv_rows(jnp.asarray(x))
        cn, sn = quantize_page(x)
        np.testing.assert_array_equal(np.asarray(cj), cn)
        np.testing.assert_array_equal(np.asarray(sj), sn)
        assert np.asarray(sj)[0, 1, 2, 0] == 1.0

    def test_dequant_error_bound(self):
        rng = np.random.default_rng(1)
        x = (5.0 * rng.standard_normal((4, 8, 16))).astype(np.float32)
        c, s = quantize_kv_rows(jnp.asarray(x))
        back = np.asarray(dequantize_pages(c, s, jnp.float32))
        bound = (np.max(np.abs(x), axis=-1, keepdims=True)
                 * KV_TIER_QUANT_RTOL + 1e-7)
        assert np.all(np.abs(back - x) <= bound)


# ---------------------------------------------------------- the sampler
class TestSampleRows:
    """``_sample_rows``, the sampler every engine's programs run: greedy
    rows are ``np.argmax`` (first occurrence on ties), temperature rows
    are ``jax.random.categorical`` at the row's own key and
    temperature."""

    @pytest.mark.parametrize("B,V", [(1, 7), (3, 37), (8, 128),
                                     (9, 257), (16, 500)])
    def test_greedy_is_argmax(self, B, V):
        logits = jax.random.normal(jax.random.PRNGKey(B * V), (B, V))
        keys = jax.random.split(jax.random.PRNGKey(1), B)
        got = _sample_rows(logits, keys, jnp.zeros((B,)))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(got), np.argmax(np.asarray(logits), -1))

    def test_greedy_first_occurrence_on_ties(self):
        # duplicate maxima: the FIRST index, as np.argmax; the serving
        # identity checks against a plain reference depend on it
        logits = jnp.zeros((4, 200)).at[:, 150].set(5.0).at[:, 30].set(5.0)
        keys = jax.random.split(jax.random.PRNGKey(2), 4)
        got = np.asarray(_sample_rows(logits, keys, jnp.zeros((4,))))
        np.testing.assert_array_equal(got, np.full(4, 30))

    def test_temperature_distribution(self):
        # sharply-biased logits at temp 1.0: the favored token must
        # dominate; a flat draw (or an argmax leak into temp rows)
        # cannot pass this
        B, V = 256, 16
        logits = jnp.zeros((B, V)).at[:, 5].set(3.0)
        keys = jax.random.split(jax.random.PRNGKey(11), B)
        toks = np.asarray(_sample_rows(logits, keys, jnp.ones((B,))))
        frac = np.mean(toks == 5)
        # softmax prob of token 5 ≈ 0.57 at these logits
        assert 0.4 < frac < 0.75
        assert len(np.unique(toks)) > 1     # it actually sampled

    def test_each_row_draws_with_its_own_key_and_temperature(self):
        """Greedy and sampled rows in one batch: a row at temperature 0
        is its argmax whatever its key; another is the categorical draw
        of ITS key at ITS temperature, so two rows with the same logits
        and key agree and the batch's order changes no row."""
        B, V = 6, 97
        logits = jax.random.normal(jax.random.PRNGKey(3), (B, V))
        logits = logits.at[4].set(logits[1])
        keys = jax.random.split(jax.random.PRNGKey(7), B)
        keys = keys.at[4].set(keys[1])
        temps = jnp.asarray([0.0, 1.0, 0.0, 0.7, 1.0, 2.0])
        got = np.asarray(_sample_rows(logits, keys, temps))
        for b in range(B):
            want = (np.argmax(np.asarray(logits[b])) if temps[b] == 0
                    else jax.random.categorical(
                        keys[b], logits[b].astype(jnp.float32) / temps[b]))
            assert got[b] == int(want), b
        assert got[4] == got[1]
        back = np.asarray(_sample_rows(logits[::-1], keys[::-1],
                                       temps[::-1]))
        np.testing.assert_array_equal(back[::-1], got)


# ------------------------------------------------ the int8-resident read
def _quant_paged_setup(seed, B, KV, Dh, P, ps, mp):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
    kq, ks = quantize_kv_rows(k)
    vq, vs = quantize_kv_rows(v)
    table = jnp.asarray(
        rng.permutation(P)[:B * mp].reshape(B, mp), jnp.int32)
    return k, v, kq, ks, vq, vs, table


class TestQuantResidentRead:
    """Over int8-resident pages every program gathers the codes its
    table names and dequantizes those: the references with ``k_scale`` /
    ``v_scale`` are the references over the dequantized pool, bit for
    bit (the dequant is elementwise), and within the codec's bound of
    the float pages."""

    HEADS = {"gqa_4_2": (4, 2), "mha_2_2": (2, 2)}

    @pytest.mark.parametrize("heads", HEADS)
    def test_decode_is_the_gather_over_the_dequantized_pool(self, heads):
        (H, KV), B, Dh, ps, mp = self.HEADS[heads], 3, 16, 8, 4
        k, v, kq, ks, vq, vs, table = _quant_paged_setup(
            0, B, KV, Dh, 16, ps, mp)
        lens = jnp.asarray([5, 17, 32], jnp.int32)      # ragged; one full
        q = jax.random.normal(jax.random.PRNGKey(1), (B, H, Dh))
        got = paged_attention_reference(q, kq, vq, table, lens,
                                        k_scale=ks, v_scale=vs)
        want = paged_attention_reference(
            q, dequantize_pages(kq, ks, jnp.float32),
            dequantize_pages(vq, vs, jnp.float32), table, lens)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # attention output error under per-row int8 KV stays within a
        # few quantization steps of the unit-scale values
        exact = paged_attention_reference(q, k, v, table, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   atol=12 * KV_TIER_QUANT_RTOL)

    @pytest.mark.parametrize("heads", HEADS)
    def test_chunk_is_the_gather_over_the_dequantized_pool(self, heads):
        (H, KV), B, C, Dh, ps, mp = self.HEADS[heads], 2, 5, 16, 8, 4
        k, v, kq, ks, vq, vs, table = _quant_paged_setup(
            4, B, KV, Dh, 16, ps, mp)
        start = jnp.asarray([3, 11], jnp.int32)         # ragged histories
        q = jax.random.normal(jax.random.PRNGKey(5), (B, C, H, Dh))
        got = paged_chunk_attention_reference(q, kq, vq, table, start,
                                              k_scale=ks, v_scale=vs)
        want = paged_chunk_attention_reference(
            q, dequantize_pages(kq, ks, jnp.float32),
            dequantize_pages(vq, vs, jnp.float32), table, start)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        exact = paged_chunk_attention_reference(q, k, v, table, start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   atol=12 * KV_TIER_QUANT_RTOL)

    @pytest.mark.parametrize("decode", [True, False],
                             ids=["decode", "chunk"])
    def test_the_rule_gathers_and_says_why(self, decode):
        kw = dict(decode=decode, tp=False, interpret=False, tokens=1024,
                  head_dim=128)
        assert paged_reader(quant=False, **kw)[0].startswith("dstpu_paged_")
        assert paged_reader(quant=True, **kw) == ("xla",
                                                  "int8-resident pages")

    def test_a_step_over_codes_refuses_a_mosaic_reader(self):
        """``paged_attention_step`` with scale planes and a reader that
        is not the gather would hand int8 codes to a kernel that reads
        them as values: it raises instead."""
        pool = jnp.zeros((1, 2, 4, 8, 16), jnp.int8)
        scale = jnp.ones((1, 2, 4, 8, 1), jnp.float32)
        x = jnp.zeros((1, 1, 2, 16))
        with pytest.raises(ValueError, match="int8-resident"):
            paged_attention_step(
                x, x, x, pool, pool, 0, jnp.zeros((1, 4), jnp.int32),
                jnp.zeros((1,), jnp.int32), continuation=False,
                prefill=False, reader="dstpu_paged_decode",
                flash_force_reference=False, kps=scale, vps=scale)


# ------------------------------------- the rule at the traffic the cells send
# What each serving cell of the benchmark hands the rule, written down
# from benchmark/configs and benchmark/workloads (not imported): the
# family's cache row (K/V heads, stored width, values, and the head's own
# width where it is padded to a tile), the engine's chunk and bucket,
# page size, and whether the rows are latent / the family steps a state.
CELLS = {
    "gpt2-1.3b.serve.chat-0.8knee": (
        CacheRow(16, 128, 128), dict(prefill_bucket=128, page_size=16), ""),
    "mixtral-8x7b-d4.serve.chat-sat": (
        CacheRow(8, 128, 128), dict(prefill_bucket=128, page_size=16), ""),
    "mixtral-8x7b-d4.serve.docs-sat": (
        CacheRow(8, 128, 128),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), ""),
    "openpangu-ultra-moe-718b-ep16-d5.serve.think-sat": (
        CacheRow(1, 576, 512, values_in_keys=True),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), "latent"),
    "qwen3-next-80b-a3b-ep8-d12.serve.docqa-sat": (
        CacheRow(2, 256, 256),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), "state"),
    "v42.granite-4.0-h-micro.serve.assist-sat": (
        CacheRow(8, 128, 128, head_width=64),
        dict(prefill_chunk=256, prefill_bucket=0, page_size=16), "state"),
    "v44.laguna-s-2.1-ep16-d13.serve.code-sat": (
        CacheRow(8, 128, 128),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), ""),
}
# cell -> (decode, chunk) readers of its build on one device
ON_ONE_DEVICE = {
    "gpt2-1.3b.serve.chat-0.8knee":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    "mixtral-8x7b-d4.serve.chat-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    "mixtral-8x7b-d4.serve.docs-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    # a latent chunk expands its rows and runs the flash kernel
    "openpangu-ultra-moe-718b-ep16-d5.serve.think-sat":
        ("dstpu_mla_decode", "xla"),
    "qwen3-next-80b-a3b-ep8-d12.serve.docqa-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    # a head of 64 in a 128-lane tile
    "v42.granite-4.0-h-micro.serve.assist-sat":
        ("dstpu_paged_decode", "xla"),
    "v44.laguna-s-2.1-ep16-d13.serve.code-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
}


def _state_block(cell):
    """The ``(Recurrent, cfg)`` a recurrent cell's build hands the rule,
    at the published widths (the default configs)."""
    from deepspeed_tpu.models import granite_hybrid, qwen3_next

    if cell.startswith("qwen3-next"):
        return qwen3_next.FAMILY.recurrent, qwen3_next.Qwen3NextConfig()
    return (granite_hybrid.FAMILY.recurrent,
            granite_hybrid.GraniteHybridConfig())


class TestTheRuleAtTheCells:
    """The table a reviewer needs to see that no cell's reader changed,
    and the one the next kernel PR edits."""

    @pytest.mark.parametrize("where", ["one_device", "tp", "interpret"])
    @pytest.mark.parametrize("cell", CELLS)
    def test_the_readers_a_cells_build_gets(self, cell, where):
        row, engine, kind = CELLS[cell]
        policy = resolve_serving_kernels(
            tp=where == "tp", interpret=where == "interpret",
            recurrent=kind == "state", chunk=(
                engine.get("prefill_chunk") or engine["prefill_bucket"],
                row.head_width or row.key_width),
            state_block=_state_block(cell) if kind == "state" else None)
        if kind == "latent":
            policy = policy._replace(decode=latent_reader(policy.decode))
        readers = (policy.decode[0], policy.chunk[0])
        # the one family that states a block of its chunked rule (PR 50)
        blocked = cell.startswith("qwen3-next")
        if where == "one_device":
            assert readers == ON_ONE_DEVICE[cell]
            assert policy.state_step == ("pallas" if kind == "state"
                                         else "xla")
            assert policy.state_chunk[0] == ("pallas" if blocked else "xla")
            assert policy.fallbacks == ()
            return
        assert policy.state_chunk[0] == "xla"
        assert policy.state_chunk[1].startswith(
            "the family states no block" if not blocked
            else "tp:" if where == "tp" else "interpret:")
        assert readers == ("xla", "xla")
        assert policy.decode[1].startswith(
            "tp:" if where == "tp" else "interpret:")
        # a mesh takes the state's kernel, visibly; off the chip it runs
        # in interpret mode
        assert policy.state_step == (
            "pallas" if kind == "state" and where == "interpret" else "xla")
        assert len(policy.fallbacks) == (where == "tp") * (
            (kind == "state") + blocked)


# --------------------------------------- nothing reads the old switches
OLD_SWITCHES = {
    "DSTPU_PAGED_ATTENTION": "xla", "DSTPU_FORCE_PAGED_PALLAS": "1",
    "DSTPU_PAGED_V1": "1", "DSTPU_FUSED_SAMPLING": "on",
    "DSTPU_FORCE_FUSED_SAMPLING": "1", "DSTPU_FORCE_ADAM_PALLAS": "1",
}
KERNELS_BLOCK = {"paged_attention": "pallas_v2", "fused_sampling": "on"}


class TestNothingReadsTheOldSwitches:
    @pytest.mark.parametrize("name", OLD_SWITCHES)
    def test_an_old_env_switch_moves_nothing(self, name, monkeypatch):
        kw = dict(interpret=False, recurrent=True, chunk=(1024, 128))
        default = resolve_serving_kernels(**kw)
        monkeypatch.setenv(name, OLD_SWITCHES[name])
        assert resolve_serving_kernels(**kw) == default
        assert default.decode[0] == "dstpu_paged_decode"

    @pytest.mark.parametrize("door", ["training_config", "serving_config",
                                      "init_serving", "init_inference",
                                      "encoder"])
    def test_a_kernels_block_is_refused_by_name(self, door, gpt2_model):
        """Not dropped silently: a user who forced a kernel learns that
        it no longer is, and where to read on."""
        cfg, params = gpt2_model
        with pytest.raises(ValueError, match="MIGRATION.md") as e:
            if door == "training_config":
                Config.from_dict({"train_batch_size": 1,
                                  "kernels": dict(KERNELS_BLOCK)})
            elif door == "serving_config":
                serving_engine(params, cfg, kernels=dict(KERNELS_BLOCK))
            elif door == "init_serving":
                init_serving(params, cfg,
                             config={"kernels": dict(KERNELS_BLOCK)})
            elif door == "init_inference":
                init_inference(apply_fn=lambda p, x: x, params=params,
                               config={"kernels": dict(KERNELS_BLOCK)})
            else:
                from deepspeed_tpu.models import bert

                serving_engine(None, bert.BertConfig.tiny(),
                               kernels={"paged_attention": "auto"})
        assert "`kernels` block is gone" in str(e.value)

    def test_no_environment_read_under_inference_or_ops(self):
        root = pathlib.Path(deepspeed_tpu.__file__).parent
        reads = [str(f.relative_to(root))
                 for d in ("inference", "ops")
                 for f in sorted((root / d).rglob("*.py"))
                 if "os.environ" in f.read_text()
                 or "getenv" in f.read_text()]
        assert reads == []
        # and the two modules that read a switch of their own are gone
        for gone in ("adam_pallas", "sampling_pallas"):
            assert importlib.util.find_spec(
                f"deepspeed_tpu.ops.{gone}") is None


# --------------------------------------------------- engine-level policy
PROMPTS = {
    "a": ([5, 9, 2], 6),
    "b": ([17, 3, 3, 8, 1], 5),
    "c": ([40, 2], 7),
}

KW = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
          prefill_bucket=8)


def serve_all(eng):
    for rid, (prompt, n_new) in PROMPTS.items():
        eng.submit(rid, prompt, max_new_tokens=n_new)
    return eng.run()


class TestEnginePolicy:
    def test_statusz_reports_the_readers_and_nothing_to_request(
            self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, telemetry=True, **KW)
        out = serve_all(eng)
        assert all(len(out[rid]) == len(p) + n
                   for rid, (p, n) in PROMPTS.items())
        kz = eng.statusz()["kernels"]
        assert sorted(kz) == ["chunk", "decode", "experts", "fallbacks",
                              "state_chunk", "state_step", "window"]
        for row in ("decode", "chunk", "window", "state_chunk"):
            reader = kz[row]["reader"]
            assert reader == "xla" or reader.startswith("dstpu_"), kz
            assert kz[row]["reason"]
        assert (kz["state_step"], kz["fallbacks"]) == ("xla", [])
        # a CPU engine gathers, so the row scatter writes a decode step's row
        assert kz["decode"]["write"] == "scatter"
        assert "interpret" in kz["decode"]["reason"]
        # the reader is /statusz's to name; what the steps dispatched
        # is the step ledger's to count (/statusz "steps"), beside the
        # fetches the registry counts
        cnt = eng.registry.snapshot()["counters"]
        names = sorted(n for n in cnt if n.startswith("serving_kernel_"))
        assert names == ["serving_kernel_fallbacks"]
        assert cnt["serving_kernel_fallbacks"] == 0
        steps = eng.statusz()["steps"]
        assert steps["programs"]["decode"][0] \
            + steps["programs"]["decode_ahead"][0] \
            >= cnt["serving_decode_syncs"] > 0
        assert steps["programs"]["prefill"][0] >= len(PROMPTS)
        (last,) = steps["rows"]
        # the last step read the decode that flew in behind the one before
        assert last["programs"]["decode"][0] \
            + last["programs"]["decode_ahead"][0] <= 1
        eng.shutdown()

    def test_zero_inference_rejects_quantized_resident(
            self, llama_model, devices):
        cfg, params = llama_model
        with pytest.raises(NotImplementedError,
                           match="quantized_resident"):
            serving_engine(
                params, cfg, prefix_cache=True,
                kv_tier={"enabled": True, "quantize_cold": True,
                         "quantized_resident": True},
                zero_inference={"enabled": True, "tier": "host"}, **KW)


def churn_prompts(vocab, groups=3, per=2, prefix_len=24, tail_len=4,
                  seed=0):
    rng = np.random.default_rng(seed)
    prefs = [rng.integers(1, vocab, prefix_len).tolist()
             for _ in range(groups)]
    out = []
    for _ in range(2):
        for p in prefs:
            for _ in range(per):
                out.append(p + rng.integers(1, vocab, tail_len).tolist())
    return out


# ------------------------------------------------ prequantized tier pool
PAGE_SHAPE = (2, 2, 8, 16)          # (L, KV, ps, Dh)


def _tier_cfg(**kw):
    kw.setdefault("enabled", True)
    return KVTierConfig.coerce(kw)


def _rand_page(seed=0):
    rng = np.random.default_rng(seed)
    return (3.0 * rng.standard_normal(PAGE_SHAPE)).astype(np.float32)


def _pool_bufs(pool, key):
    names, shapes, dtypes = pool.entry_meta(key)
    bufs = [pool.get_submit(n, s, d)
            for n, s, d in zip(names, shapes, dtypes)]
    pool.fence_reads()
    return bufs


class TestPrequantizedPool:
    """demote_prequantized / decode_quantized: the codes the device
    holds are the codes the tier stores are the codes a promotion
    publishes — verbatim, checksum-verified, no requantization step
    anywhere in the round trip."""

    def test_codes_roundtrip_verbatim(self):
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(quantize_cold=True), PAGE_SHAPE,
                          np.float32)
        kq, ks = quantize_page(_rand_page(1))
        vq, vs = quantize_page(_rand_page(2))
        assert pool.demote_prequantized(b"P", kq, ks, vq, vs) == "host"
        rkq, rks, rvq, rvs = pool.decode_quantized(
            b"P", _pool_bufs(pool, b"P"))
        np.testing.assert_array_equal(rkq, kq)
        np.testing.assert_array_equal(rvq, vq)
        np.testing.assert_array_equal(rks, ks)
        np.testing.assert_array_equal(rvs, vs)

    def test_interchangeable_with_host_quantize(self):
        # a prequantized demote and a host-side quantize of the same
        # values must produce interchangeable entries
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(quantize_cold=True), PAGE_SHAPE,
                          np.float32)
        k, v = _rand_page(3), _rand_page(4)
        pool.demote(b"H", k, v)
        kq, ks = quantize_page(k)
        vq, vs = quantize_page(v)
        pool.demote_prequantized(b"D", kq, ks, vq, vs)
        h = pool.decode_quantized(b"H", _pool_bufs(pool, b"H"))
        d = pool.decode_quantized(b"D", _pool_bufs(pool, b"D"))
        for a, b in zip(h, d):
            np.testing.assert_array_equal(a, b)

    def test_dense_entry_rejected(self):
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(), PAGE_SHAPE, np.float32)
        pool.demote(b"X", _rand_page(5), _rand_page(6))
        with pytest.raises(ValueError, match="dense entry"):
            pool.decode_quantized(b"X", _pool_bufs(pool, b"X"))
        kq, ks = quantize_page(_rand_page(7))
        with pytest.raises(ValueError, match="quantize_cold"):
            pool.demote_prequantized(b"Y", kq, ks, kq, ks)

    def test_corruption_caught_before_publish(self):
        from deepspeed_tpu.faults import ChecksumError
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(quantize_cold=True), PAGE_SHAPE,
                          np.float32)
        kq, ks = quantize_page(_rand_page(8))
        vq, vs = quantize_page(_rand_page(9))
        pool.demote_prequantized(b"C", kq, ks, vq, vs)
        entry = pool.entries[b"C"]
        entry.data[0].flat[0] ^= 0x7F        # torn-write stand-in
        with pytest.raises(ChecksumError):
            pool.decode_quantized(b"C", _pool_bufs(pool, b"C"))


# ---------------------------------------------------- quantized_resident
class TestQuantizedResident:
    """int8-resident promoted pages: promotions publish stored codes
    directly (no dequant→scatter), counter-verified and leak-checked.
    Token identity vs the dense engine is NOT the contract here — the
    resident cache itself is int8 under the documented rtol — the
    contract is completion + verbatim code motion + zero page leaks."""

    QRES = {"enabled": True, "quantize_cold": True,
            "quantized_resident": True}

    @pytest.mark.slow
    def test_promote_path_counters_and_leaks(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=19)
        eng = serving_engine(params, cfg, prefix_cache=True,
                             kv_tier=dict(self.QRES), max_batch=2,
                             page_size=8, num_pages=12, max_seq=64,
                             prefill_bucket=8)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=6)
        outs = eng.run()
        assert len(outs) == len(prompts)
        # run() returns prompt + generated: every request decoded its
        # full budget off the int8-resident cache
        assert all(len(outs[i]) == len(p) + 6
                   for i, p in enumerate(prompts))
        cnt = eng.registry.snapshot()["counters"]
        # pages moved through the tier AND the promotions published
        # int8 codes directly (the dequant-scatter was skipped)
        assert cnt["kv_tier_demoted_pages"] > 0
        assert cnt["kv_tier_promoted_pages"] > 0
        assert cnt["kv_tier_quant_resident_promotes"] > 0
        assert eng.check_leaks() == []
        kz = eng.statusz()["kv_tier"]
        assert kz["quantized_resident"] is True
        # the device cache really is int8 + f32 scales
        assert eng.cache.k.dtype == jnp.int8
        assert eng.cache.k_scale.dtype == jnp.float32
