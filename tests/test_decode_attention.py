"""``decode_attention`` contracts each GQA group against the bf16 cache as
stored.  It must give what the plain formulation gives: KV heads repeated
up to the query heads, the whole cache upcast to f32, one score row per
head.  That reference lives here, independent of the code under test.

Activations run in f32 for the comparison (``ACT_DTYPE`` patched), so that
a bf16 rounding of the output neither hides nor fakes a difference; the
cache stays bf16, as it is served.  Both sides are jitted, so XLA's fused
elementwise code (RoPE, exp) is the same on both."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import common as C

B, S_MAX, N_KV, DH, D = 3, 16, 2, 16, 32
THETA = 10000.0


def _params(key, nq):
    ks = jax.random.split(key, 4)

    def lin(k, din, dout):
        w = jax.random.normal(k, (din, dout), jnp.float32) / np.sqrt(din)
        return lambda x: x.astype(jnp.float32) @ w

    return {"wq": lin(ks[0], D, nq * DH), "wk": lin(ks[1], D, N_KV * DH),
            "wv": lin(ks[2], D, N_KV * DH), "wo": lin(ks[3], nq * DH, D)}


def _reference(params, x, dims, cache_k, cache_v, *, position, window,
               slot, valid):
    """Write the new KV, repeat it to every query head, attend in f32."""
    pos = jnp.reshape(position, (-1, 1)) * jnp.ones((B, 1), jnp.int32)
    q, k, v = C._qkv(params, x, dims, pos, THETA)
    idx = np.broadcast_to(np.asarray(slot), (B,))
    if window is not None:
        idx = idx % S_MAX
    rows = np.arange(B)
    cache_k = cache_k.at[rows, idx].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[rows, idx].set(v[:, 0].astype(cache_v.dtype))
    kk = jnp.repeat(cache_k, dims.group, axis=2).astype(jnp.float32)
    vv = jnp.repeat(cache_v, dims.group, axis=2).astype(jnp.float32)
    q32 = q.astype(jnp.float32) / np.sqrt(DH)
    s = jnp.einsum("bqhd,bshd->bhqs", q32, kk,
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(jnp.asarray(valid)[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, vv,
                     precision=jax.lax.Precision.HIGHEST)
    out = out.reshape(B, 1, dims.n_q * DH).astype(C.ACT_DTYPE)
    return params["wo"](out).astype(C.ACT_DTYPE), cache_k, cache_v


def _case(mode):
    """(kwargs for decode_attention, slot, visibility [B, S_max])."""
    kv_pos = np.arange(S_MAX)
    if mode == "scalar":
        position = 9
        return ({"position": jnp.int32(position)}, position,
                np.broadcast_to(kv_pos <= position, (B, S_MAX)))
    if mode == "slot":
        # right-padded rows: each sees its own real prefix, not its
        # padding, then the suffix it generated up to its slot
        real = np.array([2, 9, 5], np.int32)
        pad_end = np.array([4, 9, 8], np.int32)
        slot = np.array([6, 11, 8], np.int32)
        position = real + slot - pad_end
        valid = ((kv_pos[None] < real[:, None])
                 | ((kv_pos[None] >= pad_end[:, None])
                    & (kv_pos[None] <= slot[:, None])))
        return ({"position": jnp.asarray(position), "slot": jnp.asarray(slot),
                 "kv_valid": jnp.asarray(valid)}, slot, valid)
    # a ring buffer that has wrapped: every slot is in the window
    position = S_MAX + 5
    return ({"position": jnp.int32(position), "window": S_MAX}, position,
            np.ones((B, S_MAX), bool))


@pytest.mark.parametrize("mode", ["scalar", "slot", "window"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_grouped_decode_matches_repeated_f32_reference(group, mode,
                                                      monkeypatch):
    monkeypatch.setattr(C, "ACT_DTYPE", jnp.float32)
    nq = N_KV * group
    dims = C.AttnDims(nq, N_KV, DH, nq, N_KV)
    assert dims.group == group
    kp, kx, kk, kv = jax.random.split(jax.random.PRNGKey(group), 4)
    params = _params(kp, nq)
    x = jax.random.normal(kx, (B, 1, D), jnp.float32)
    cache_k = jax.random.normal(kk, (B, S_MAX, N_KV, DH)).astype(jnp.bfloat16)
    cache_v = jax.random.normal(kv, (B, S_MAX, N_KV, DH)).astype(jnp.bfloat16)
    kwargs, slot, valid = _case(mode)

    got = jax.jit(lambda ck, cv: C.decode_attention(
        params, x, dims, ck, cv, rope_theta=THETA, **kwargs))(cache_k, cache_v)
    want = jax.jit(lambda ck, cv: _reference(
        params, x, dims, ck, cv, position=kwargs["position"],
        window=kwargs.get("window"), slot=slot, valid=valid))(cache_k, cache_v)

    out, ref = np.asarray(got[0]), np.asarray(want[0])
    assert out.dtype == np.float32
    assert np.max(np.abs(out - ref)) <= 1e-5 * np.max(np.abs(ref))
    for new, old in zip(got[1:], want[1:]):
        assert new.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
