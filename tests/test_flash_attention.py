"""Flash-attention kernel parity vs the XLA reference path (interpret mode
on CPU; the same kernel compiles via Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.ops.attention import xla_attention
from cassmantle_tpu.ops.flash_attention import (
    BLOCK_K,
    BLOCK_Q,
    flash_attention,
    flash_attention_ok,
)


def _rand_qkv(key, batch, seq, heads, dim, dtype=jnp.float32, seq_k=None):
    ks = jax.random.split(key, 3)
    seq_k = seq_k or seq
    q = jax.random.normal(ks[0], (batch, seq, heads, dim), dtype)
    k = jax.random.normal(ks[1], (batch, seq_k, heads, dim), dtype)
    v = jax.random.normal(ks[2], (batch, seq_k, heads, dim), dtype)
    return q, k, v


def test_ok_predicate():
    q, k, _ = _rand_qkv(jax.random.PRNGKey(0), 1, BLOCK_Q, 2, 64)
    assert flash_attention_ok(q, k)
    q2, k2, _ = _rand_qkv(jax.random.PRNGKey(0), 1, 77, 2, 64)
    assert not flash_attention_ok(q2, k2)  # not block-divisible
    q3 = q[0]
    assert not flash_attention_ok(q3, k[0])  # needs batch dim


@pytest.mark.parametrize("seq,heads,dim", [
    (BLOCK_Q, 2, 64),          # single block
    (2 * BLOCK_Q, 1, 40),      # SD1.5 head_dim at level 0, 2 k-blocks
    (4 * BLOCK_Q, 2, 80),      # multi-block, SD1.5 level-1 head_dim
])
def test_flash_matches_xla(seq, heads, dim):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 2, seq, heads, dim)
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_cross_lengths():
    """Sq != Sk (both block-divisible)."""
    q, k, v = _rand_qkv(
        jax.random.PRNGKey(2), 1, BLOCK_Q, 2, 64, seq_k=2 * BLOCK_K
    )
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_bf16():
    q, k, v = _rand_qkv(
        jax.random.PRNGKey(3), 1, BLOCK_Q, 2, 64, dtype=jnp.bfloat16
    )
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_flash_extreme_logits_stable():
    """Online softmax must survive large logit magnitudes."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, BLOCK_Q, 1, 64)
    q = q * 30.0
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
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


def test_flash_cross_ragged_kv_matches_xla():
    """Ragged-S_k cross-attention (the UNet's text context, S_k=77):
    K/V pad into one block and the kernel's kv_len mask makes the
    result EXACT vs the XLA reference — pad columns contribute
    nothing to the softmax."""
    from cassmantle_tpu.ops.flash_attention import (
        flash_cross_attention,
        flash_cross_ok,
    )

    for sk in (77, 7, 130):
        q, k, v = _rand_qkv(jax.random.PRNGKey(5), 2, BLOCK_Q, 2, 40,
                            seq_k=sk)
        assert flash_cross_ok(q, k), sk
        out = flash_cross_attention(q, k, v, interpret=True)
        ref = xla_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"{sk=}")


def test_flash_cross_ok_predicate():
    from cassmantle_tpu.ops.flash_attention import (
        CROSS_BLOCK_K,
        flash_cross_ok,
    )

    q, k, _ = _rand_qkv(jax.random.PRNGKey(6), 1, BLOCK_Q, 2, 64,
                        seq_k=77)
    assert flash_cross_ok(q, k)
    # short ALIGNED S_k (128..896) also belongs here: too small for the
    # plain kernel's 1024-blocks, still worth keeping out of HBM
    q2, k2, _ = _rand_qkv(jax.random.PRNGKey(6), 1, BLOCK_Q, 2, 64,
                          seq_k=CROSS_BLOCK_K)
    assert flash_cross_ok(q2, k2)
    from cassmantle_tpu.ops.flash_attention import flash_cross_attention

    out = flash_cross_attention(q2, k2, k2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xla_attention(q2, k2, k2)),
        atol=2e-5, rtol=2e-5)
    # full-block K/V stays with the plain kernel
    q4, k4, _ = _rand_qkv(jax.random.PRNGKey(6), 1, BLOCK_Q, 2, 64)
    assert not flash_cross_ok(q4, k4)
    # short query axis -> XLA path
    q3, k3, _ = _rand_qkv(jax.random.PRNGKey(6), 1, 64, 2, 64, seq_k=77)
    assert not flash_cross_ok(q3, k3)


def test_dispatcher_routes_ragged_cross_attention():
    """multi_head_attention with use_flash=True and ragged K/V must hit
    the cross kernel (numerics equal XLA) rather than falling back."""
    from cassmantle_tpu.ops.attention import multi_head_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 1, BLOCK_Q, 2, 40,
                        seq_k=77)
    out = multi_head_attention(q, k, v, use_flash=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_cross_kill_switch(monkeypatch):
    """CASSMANTLE_NO_FLASH_CROSS reverts ragged cross-attention to the
    XLA path (operator insurance for a misbehaving kernel). Routing is
    asserted directly: the cross kernel must not be INVOKED when the
    switch is set ('0' and unset mean on), since the two paths are
    parity-equal by design and output comparison can't see routing."""
    import cassmantle_tpu.ops.flash_attention as fa_mod
    from cassmantle_tpu.ops.attention import multi_head_attention

    calls = []
    real = fa_mod.flash_cross_attention
    monkeypatch.setattr(
        fa_mod, "flash_cross_attention",
        lambda *a, **kw: calls.append(1) or real(*a, **kw))

    q, k, v = _rand_qkv(jax.random.PRNGKey(8), 1, BLOCK_Q, 2, 40,
                        seq_k=77)
    monkeypatch.setenv("CASSMANTLE_NO_FLASH_CROSS", "1")
    off = multi_head_attention(q, k, v, use_flash=True)
    assert not calls, "kill switch set but cross kernel was invoked"
    monkeypatch.setenv("CASSMANTLE_NO_FLASH_CROSS", "0")  # conventional re-enable
    on = multi_head_attention(q, k, v, use_flash=True)
    assert calls, "switch '0' must mean enabled"
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq_k", [BLOCK_Q, 77], ids=["self", "cross77"])
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
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), 4, BLOCK_Q, 2, 40,
                        seq_k=seq_k)
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
