"""A training step runs the flash forward kernel ONCE a layer (PR 62).

``checkpoint_dots`` sees no product in a ``pallas_call`` and nothing a
custom VJP's forward rule makes is saveable, so until PR 62 every
``jax.checkpoint``-ed backward ran ``dstpu_flash_fwd`` a second time to
rebuild the VJP's residuals.  The kernel now runs outside any VJP, its
context and log-sum carry names (``attention_pallas_bwd.FLASH_NAMES``) and every policy
of ``remat.py`` that keeps anything keeps them.  Interpret mode on the
CPU: the counts are read off the gradient's jaxpr, nothing runs but the
one case that compares numbers.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import topology
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.ops import attention_pallas as AP
from deepspeed_tpu.topology import MeshSpec

B, T = 4, 256


@pytest.fixture
def kernel_path(monkeypatch):
    """``flash_attention`` takes the Pallas kernel (interpret mode) as it
    does on a chip; the test steers it, the program has no switch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(AP, "flash_attention_tpu", functools.partial(
        AP.flash_attention_tpu, interpret=True))


def _gradient_jaxpr(remat: str) -> str:
    cfg = gpt2.GPT2Config(vocab_size=64, dim=256, n_layers=2, n_heads=2,
                          max_seq_len=T, remat=remat)
    params = jax.eval_shape(
        lambda: gpt2.init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((B, T + 1), jnp.int32)
    return str(jax.make_jaxpr(jax.grad(gpt2.loss_fn(cfg)))(
        params, {"tokens": tokens}))


@pytest.mark.parametrize("sharded", [False, True], ids=["alone", "shard_map"])
@pytest.mark.parametrize("remat,forwards", [
    ("save_dots", 1), ("save_dots_no_batch", 1), ("save_attn", 1),
    ("offload_attn", 1), ("offload_dots_no_batch", 1), ("full", 2)])
def test_forward_kernel_calls_in_a_checkpointed_gradient(
        kernel_path, monkeypatch, remat, forwards, sharded):
    """The layer scan's forward body holds the kernel once; the backward
    body holds it again only where the policy keeps nothing (the two
    ``offload_*`` policies park the named results on the host).  ``sharded``:
    under ``shard_map`` over a 4-device mesh, the training cell's path
    (``ops/attention.py``)."""
    # (set either way: an engine built earlier in this process leaves its
    # mesh published)
    monkeypatch.setattr(topology, "_CURRENT_MESH", MeshSpec.build(
        {"data": 4}, devices=jax.devices()[:4]) if sharded else None)
    text = _gradient_jaxpr(remat)
    assert ("shard_map" in text) == sharded
    assert text.count("name=dstpu_flash_fwd") == forwards
    # one backward a layer body either way, and it is the fused one
    assert text.count("name=dstpu_flash_bwd") == 1
    assert "dstpu_flash_bwd_dq" not in text


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _parents(q, k, v, heads, kv_heads):
    """The VJP as it stood before PR 62, from the pieces that did not
    change: the forward kernel inside the rule, the split backward."""
    return _parents_fwd(q, k, v, heads, kv_heads)[0]


def _parents_fwd(q, k, v, heads, kv_heads):
    out, lse = AP._flash_fwd_impl(
        q, k, v, None, causal=True, block_q=128, block_k=128, heads=heads,
        kv_heads=kv_heads, interpret=True)
    return out, (q, k, v, out, lse)


def _parents_bwd(heads, kv_heads, res, do):
    q, k, v, out, lse = res
    return AP._flash_bwd_impl(
        q, k, v, None, out, lse, do, causal=True, block_q=128, block_k=128,
        heads=heads, kv_heads=kv_heads, interpret=True)


_parents.defvjp(_parents_fwd, _parents_bwd)


@pytest.mark.parametrize("remat", ["none", "save_dots"])
def test_split_path_gradients_are_the_parents_bit_for_bit(remat):
    """Taking the forward kernel out of the VJP moves no number: where
    the rule answers ``split`` (a T of three 128-row blocks, and GQA), the
    gradients in float32 equal the old VJP's bit for bit, with and
    without ``jax.checkpoint``.  (Where it answers ``fused`` the sums run
    in another order: ``test_attention_pallas.py`` holds those to the
    reference.)"""
    from deepspeed_tpu.remat import checkpoint_block

    H, KV, T, D = 4, 2, 384, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, T, H, D))
    k = jax.random.normal(ks[1], (2, T, KV, D))
    v = jax.random.normal(ks[2], (2, T, KV, D))

    def new(q, k, v):
        return AP.flash_attention_tpu(q, k, v, interpret=True)

    def old(q, k, v):
        flat = lambda a: a.transpose(0, 2, 1, 3).reshape(-1, T, D)
        out = _parents(flat(q), flat(k), flat(v), H, KV)
        return out.reshape(2, H, T, D).transpose(0, 2, 1, 3)

    def grads(f):
        f = checkpoint_block(f, remat)
        return jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(
            q, k, v)

    for a, b in zip(grads(new), grads(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,path,why", [
    (256, "fused", "a_head_resident"), (384, "split", "T_is_not_whole")])
def test_the_build_span_says_which_backward_the_program_runs(T, path, why):
    """The counter that the fused backward engaged is the tracing the
    repo has: the rule's answer and reason are words of the build span
    the gradient is traced under (the training engine's ``build_step``),
    said on JAX's event bus where the VJP's backward rule asks the rule
    (the kernels import nothing of ``devprof``)."""
    from deepspeed_tpu.devprof import BUILD_LEDGER, ProgramSpan
    from deepspeed_tpu.telemetry import MetricsRegistry

    def step(q):
        return jax.grad(lambda q: jnp.sum(AP.flash_attention_tpu(
            q, q, q, interpret=True)))(q)

    step.__name__ = step.__qualname__ = f"dstpu_t_flash_{path}"
    with ProgramSpan(MetricsRegistry().span("build_step"))("train_step"):
        jax.jit(step)(jnp.ones((1, T, 1, 128), jnp.float32))
    (entry,) = [e for e in BUILD_LEDGER.snapshot()["entries"]
                if e["program"] == step.__name__]
    assert entry["span"].startswith(
        f"train_step flash_bwd={path} flash_bwd_why={why}"), entry["span"]
