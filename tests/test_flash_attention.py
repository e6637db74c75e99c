"""Flash-attention kernel parity vs the XLA reference path (interpret mode
on CPU; the same kernel compiles via Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.ops.attention import xla_attention
from cassmantle_tpu.ops.flash_attention import (
    MIN_SEQ,
    FlashPlan,
    flash_attention,
    flash_plan,
)

S = 1024  # one level-1 sequence: every plan below tiles it


def _rand_qkv(key, batch, seq, heads, dim, dtype=jnp.float32, seq_k=None):
    ks = jax.random.split(key, 3)
    seq_k = seq_k or seq
    q = jax.random.normal(ks[0], (batch, seq, heads, dim), dtype)
    k = jax.random.normal(ks[1], (batch, seq_k, heads, dim), dtype)
    v = jax.random.normal(ks[2], (batch, seq_k, heads, dim), dtype)
    return q, k, v


def test_plan_predicate():
    q, k, _ = _rand_qkv(jax.random.PRNGKey(0), 1, S, 2, 64)
    assert flash_plan(q, k).kind == "flash_self"
    q2, k2, _ = _rand_qkv(jax.random.PRNGKey(0), 1, 77, 2, 64)
    assert flash_plan(q2, k2) is None  # query axis does not tile
    assert flash_plan(q[0], k[0]) is None  # needs batch dim


@pytest.mark.parametrize("seq,heads,dim", [
    (MIN_SEQ, 2, 64),     # shortest sequence the kernel takes
    (S, 8, 40),           # SD1.5 level 0 heads, H·D = 320
    (S, 8, 80),           # SD1.5 level 1 heads, H·D = 640
    (S, 10, 64),          # SDXL level 1 heads
    (S, 20, 64),          # SDXL level 2 heads, H·D = 1280
    (S, 1, 512),          # the VAE mid block's one wide head
    (2 * S, 1, 40),       # several q blocks
])
def test_flash_matches_xla(seq, heads, dim):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 2, seq, heads, dim)
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("block_q,block_k", [
    (256, 1024),    # one K/V block: the softmax is whole, no running state
    (512, 512),     # two K/V blocks: online softmax, head state in scratch
    (1024, 256),    # four K/V blocks under one q block
])
def test_flash_blocks_agree(block_q, block_k):
    """Whatever blocks a plan names, the result is the reference's: the
    single-block body and the online-softmax body are one arithmetic."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(10), 2, S, 8, 40)
    out = flash_attention(
        q, k, v, interpret=True,
        plan=FlashPlan("flash_self", block_q, block_k))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xla_attention(q, k, v)),
        atol=2e-5, rtol=2e-5)


def test_flash_cross_lengths():
    """Sq != Sk (both block-divisible)."""
    q, k, v = _rand_qkv(
        jax.random.PRNGKey(2), 1, S, 2, 64, seq_k=2 * S
    )
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("heads,dim,seq_k", [
    (2, 64, None), (8, 40, None), (8, 40, 77)],
    ids=["self_2x64", "self_8x40", "cross77_8x40"])
def test_flash_bf16(heads, dim, seq_k):
    q, k, v = _rand_qkv(
        jax.random.PRNGKey(3), 1, S, heads, dim, dtype=jnp.bfloat16,
        seq_k=seq_k)
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )


@pytest.mark.parametrize("plan", [
    None, FlashPlan("flash_self", 512, 256)], ids=["whole", "online"])
def test_flash_extreme_logits_stable(plan):
    """The softmax must survive large logit magnitudes, in one K/V block
    and across several."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, S, 2, 64)
    q = q * 30.0
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True, plan=plan)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4
    )


def test_causal_decode_alignment():
    """causal=True with s_q != s_k (cached decode: queries are the LAST
    s_q positions) must use a bottom-right-aligned band — the single last
    query sees every key, and the general case matches a full-sequence
    causal run restricted to its last rows."""
    from cassmantle_tpu.ops.attention import multi_head_attention as attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 2, 6, 2, 8)
    full = attention(q, k, v, causal=True, use_flash=False)
    # decode step: last query only, full KV — equals last row of full run
    one = attention(q[:, -1:], k, v, causal=True, use_flash=False)
    np.testing.assert_allclose(
        np.asarray(one), np.asarray(full[:, -1:]), atol=1e-6, rtol=1e-6)
    # chunked decode: last 3 queries vs full KV
    tail = attention(q[:, -3:], k, v, causal=True, use_flash=False)
    np.testing.assert_allclose(
        np.asarray(tail), np.asarray(full[:, -3:]), atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("heads,dim", [(8, 40), (8, 80)],
                         ids=["hd320", "hd640"])
@pytest.mark.parametrize("seq_k", [77, 128, 7, 130])
def test_flash_cross_ragged_kv_matches_xla(seq_k, heads, dim):
    """Ragged-S_k cross-attention (the UNet's text context, S_k=77):
    K/V pad into 128-wide blocks and the kernel's kv_len mask makes the
    result EXACT vs the XLA reference — pad columns contribute
    nothing to the softmax. 128 needs no pad and no mask; 130 pads into
    a second block."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 2, S, heads, dim,
                        seq_k=seq_k)
    assert flash_plan(q, k).kind == "flash_cross"
    out = flash_attention(q, k, v, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_cross_plan_predicate():
    q, k, _ = _rand_qkv(jax.random.PRNGKey(6), 1, S, 2, 64, seq_k=77)
    assert flash_plan(q, k).kind == "flash_cross"
    # short ALIGNED S_k (128..384) also belongs here: too small to be
    # worth whole blocks of its own, still worth keeping out of HBM
    q2, k2, _ = _rand_qkv(jax.random.PRNGKey(6), 1, S, 2, 64, seq_k=128)
    assert flash_plan(q2, k2) == FlashPlan("flash_cross", S, 128)
    # K/V that tile are self-attention's plan, whatever S_q is
    q4, k4, _ = _rand_qkv(jax.random.PRNGKey(6), 1, S, 2, 64)
    assert flash_plan(q4, k4).kind == "flash_self"
    # short query axis -> XLA path
    q3, k3, _ = _rand_qkv(jax.random.PRNGKey(6), 1, 64, 2, 64, seq_k=77)
    assert flash_plan(q3, k3) is None
    # K/V too long to pad, too ragged to tile -> XLA path
    q5, k5, _ = _rand_qkv(jax.random.PRNGKey(6), 1, S, 2, 64, seq_k=1100)
    assert flash_plan(q5, k5) is None
    with pytest.raises(ValueError, match="no flash plan"):
        flash_attention(q5, k5, k5, interpret=True)


def test_dispatcher_routes_ragged_cross_attention():
    """multi_head_attention with use_flash=True and ragged K/V must hit
    the kernel (numerics equal XLA) rather than falling back."""
    from cassmantle_tpu.ops.attention import multi_head_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 1, S, 2, 40, seq_k=77)
    out = multi_head_attention(q, k, v, use_flash=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_cross_kill_switch(monkeypatch):
    """CASSMANTLE_NO_FLASH_CROSS reverts ragged cross-attention to the
    XLA path (operator insurance for a misbehaving kernel) and leaves
    self-attention on the kernel. Routing is asserted directly: the
    kernel must not be INVOKED for a cross site when the switch is set
    ('0' and unset mean on), since the two paths are parity-equal by
    design and output comparison can't see routing."""
    import cassmantle_tpu.ops.flash_attention as fa_mod
    from cassmantle_tpu.ops.attention import multi_head_attention

    calls = []
    real = fa_mod.flash_attention
    monkeypatch.setattr(
        fa_mod, "flash_attention",
        lambda *a, **kw: calls.append(kw["plan"].kind) or real(*a, **kw))

    q, k, v = _rand_qkv(jax.random.PRNGKey(8), 1, S, 2, 40, seq_k=77)
    monkeypatch.setenv("CASSMANTLE_NO_FLASH_CROSS", "1")
    off = multi_head_attention(q, k, v, use_flash=True)
    assert not calls, "kill switch set but the kernel took a cross site"
    multi_head_attention(q, q, q, use_flash=True)
    assert calls == ["flash_self"], "the switch is for cross sites only"
    monkeypatch.setenv("CASSMANTLE_NO_FLASH_CROSS", "0")  # conventional re-enable
    on = multi_head_attention(q, k, v, use_flash=True)
    assert calls[-1] == "flash_cross", "switch '0' must mean enabled"
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq_k", [S, 77], ids=["self", "cross77"])
def test_dispatch_per_batch_shard_matches_unsharded(seq_k):
    """Inside ``batch_sharded_kernels`` (the dp serving mesh) each device
    runs the kernel on its own batch rows through shard_map; attention
    never mixes rows, so the result is the unsharded one. The kernels
    run in interpret mode here; tests/test_tpu_compile.py asks the
    chip's compiler for the same dispatch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cassmantle_tpu.ops.attention import (
        batch_sharded_kernels,
        multi_head_attention,
    )

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), 4, S, 2, 40, seq_k=seq_k)
    rows = NamedSharding(mesh, P("dp"))

    def attend(q, k, v):
        with batch_sharded_kernels(mesh, "dp"):
            return multi_head_attention(q, k, v, use_flash=True)

    out = jax.jit(attend, out_shardings=rows)(
        *(jax.device_put(t, rows) for t in (q, k, v)))
    assert len(out.sharding.device_set) == 4
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xla_attention(q, k, v)),
        atol=2e-5, rtol=2e-5)


def test_flash_vae_attention_parity_and_gate():
    """The VAE mid block's single-head, full-channel-width attention
    takes the flash kernel as a self-attention site of one wide head;
    numeric parity vs the XLA path, and the gate keeps ragged
    sequences off the kernel."""
    from cassmantle_tpu.ops.attention import multi_head_attention

    q = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 1, 320),
                          jnp.float32)
    assert flash_plan(q, q).kind == "flash_self"
    ref = multi_head_attention(q, q, q, use_flash=False)
    out = multi_head_attention(q, q, q, use_flash=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    # production width and narrow heads are the same plan kind; a
    # ragged S stays XLA, and so does a head wider than the kernel's tile
    q_wide = jnp.zeros((1, 4096, 1, 512))
    q_narrow = jnp.zeros((1, 1024, 1, 64))
    assert (flash_plan(q_wide, q_wide).kind
            == flash_plan(q_narrow, q_narrow).kind == "flash_self")
    q_ragged = jnp.zeros((1, 500, 1, 320))
    assert flash_plan(q_ragged, q_ragged) is None
    q_fat = jnp.zeros((1, 1024, 1, 2048))
    assert flash_plan(q_fat, q_fat) is None
