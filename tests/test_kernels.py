"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# (B, D) matrices, D an int: (300, 3000) / (300, 5000) have true
# multi-block tails on both grid axes — the tail-tile leak regression.
# Leaf shapes, D a tuple: the kernel takes (B, *D) in place, viewed as
# (B, R, C) — conv leaves, a last dim past one lane tile, a bias, the
# B=1 microbatch row, and batch tails no block divides ((300, 3x3x16x24)
# and (13, 264x256) end on a part-filled batch block; (13, 264x256) also
# ends on a part-filled row block).
@pytest.mark.parametrize("B,D", [(8, 256), (64, 1000), (256, 4096), (5, 131),
                                 (300, 3000), (300, 5000), (257, 2049),
                                 (5, (3, 3, 3, 8)), (300, (3, 3, 16, 24)),
                                 (7, (6, 130)), (9, (64,)), (1, (33, 257)),
                                 (13, (264, 256))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fim_diag_kernel(B, D, dtype):
    leaf = D if isinstance(D, tuple) else (D,)
    key = jax.random.PRNGKey(B * int(np.prod(leaf)))
    g = jax.random.normal(key, (B, *leaf), dtype)
    old = jax.random.uniform(jax.random.PRNGKey(1), leaf, jnp.float32)
    out_k = ops.fim_diag_update(g, old, 0.9, force_kernel=True)
    out_r = ref.fim_diag_ref(g, old, 0.9)
    assert out_k.shape == leaf
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,D", [(5, 512), (21, 4096), (21, 10_001), (9, 64),
                                 (9, 12_300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vlbfgs_gram_kernel(n, D, dtype):
    key = jax.random.PRNGKey(n + D)
    basis = jax.random.normal(key, (n, D), dtype)
    gk = np.asarray(ops.vlbfgs_gram(basis, force_kernel=True))
    gr = np.asarray(ref.vlbfgs_gram_ref(basis))
    scale = max(np.abs(gr).max(), 1.0)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(gk / scale, gr / scale, rtol=tol, atol=tol)


FLASH_CASES = [
    # B, H, KV, S, hd, causal, window
    (1, 4, 2, 256, 64, True, 0),
    (2, 8, 8, 128, 32, True, 0),    # MHA
    (1, 8, 1, 256, 64, True, 0),    # MQA
    (1, 4, 4, 256, 64, True, 96),   # sliding window
    (1, 2, 1, 128, 64, False, 0),   # encoder (non-causal)
]


@pytest.mark.parametrize("B,H,KV,S,hd,causal,window", FLASH_CASES)
def test_flash_attention_kernel(B, H, KV, S, hd, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32)
    out_k = ops.flash_attention(q, k, v, causal=causal, window=window,
                                force_kernel=True)
    out_r = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.bfloat16)
    out_k = ops.flash_attention(q, k, v, force_kernel=True).astype(jnp.float32)
    out_r = ref.flash_attention_ref(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=5e-2, atol=5e-2)


# ------------------------------------------------------- codec kernels
@pytest.mark.parametrize("shape", [(7,), (1000,), (33, 129), (4096,),
                                   (300, 17), (3, 700, 129)])
def test_int8_roundtrip_kernel_bit_identical_to_oracle(shape):
    """The fused int8 kernel and the jnp oracle consume the same uniform
    draws, so they must agree bit-for-bit (not allclose)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(int(np.prod(shape))))
    x = jax.random.normal(k1, shape) * 3.0
    u = jax.random.uniform(k2, shape)
    from repro.kernels import codec_ops
    scale = ref.int8_scale(x)
    out_k = codec_ops.int8_roundtrip(x, u, scale, interpret=True)
    out_r = ref.int8_roundtrip_ref(x, u, scale)
    assert out_k.shape == x.shape
    assert bool(jnp.all(out_k == out_r))


@pytest.mark.parametrize("n,k", [(8, 2), (35, 4), (1000, 100), (5000, 1),
                                 (2048, 2048), (1537, 700), (1024, 1),
                                 (100_000, 999)])
def test_topk_select_kernel_bit_identical_to_oracle(n, k):
    """Histogram + threshold-select kernel vs the jnp oracle: identical
    integer bucket logic, so keep masks match bit-for-bit and exactly k
    coordinates survive (the wire_bytes billing invariant)."""
    flat = jax.random.normal(jax.random.PRNGKey(n + k), (n,))
    from repro.kernels import codec_ops
    out_k = codec_ops.topk_select(flat, k, interpret=True)
    out_r = ref.topk_select_ref(flat, k)
    assert bool(jnp.all(out_k == out_r))
    assert int(jnp.sum(out_k != 0)) == k
    # magnitude correctness: every kept |x| dominates every dropped |x|
    # up to the radix tie band (< 1.5x by construction)
    absx = jnp.abs(flat)
    kept = out_k != 0
    mn_kept = float(jnp.min(jnp.where(kept, absx, jnp.inf)))
    mx_drop = float(jnp.max(jnp.where(kept, -jnp.inf, absx))) if k < n else 0.0
    assert mn_kept * 1.5 >= mx_drop


def test_topk_select_handles_threshold_ties():
    """Duplicate magnitudes on the threshold bucket break by index order
    — still exactly k kept, and kernel == oracle on the chosen set."""
    from repro.kernels import codec_ops
    flat = jnp.asarray([3.0, -1.0, 1.0, 1.0, -3.0, 1.0, 0.5, -1.0])
    for k in (1, 2, 3, 4, 5, 8):
        out_k = codec_ops.topk_select(flat, k, interpret=True)
        out_r = ref.topk_select_ref(flat, k)
        assert bool(jnp.all(out_k == out_r)), k
        assert int(jnp.sum(out_k != 0)) == k


@pytest.mark.parametrize("k", [1, 50_001, 80_000, 120_000])
def test_topk_select_ties_span_blocks(k):
    """A tie bucket spread over several (rows, 128) blocks: the rank
    carried across the sequential grid keeps the first ``need`` ties in
    index order, as the oracle does."""
    from repro.kernels import codec_ops
    flat = jnp.tile(jnp.asarray([1.0, -1.0, 0.5]), 40_000)
    out_k = codec_ops.topk_select(flat, k, interpret=True)
    assert bool(jnp.all(out_k == ref.topk_select_ref(flat, k)))
    assert int(jnp.sum(out_k != 0)) == k


def test_topk_select_matches_sort_semantics():
    """On distinct magnitudes the bucketed select must reproduce the
    exact jax.lax.top_k set whenever no two survivors share the
    threshold bucket — checked here with well-separated values."""
    vals = jnp.asarray([1.0, -8.0, 0.5, 3.0, -0.1, 0.2, 6.0, -2.0])
    got = ref.topk_select_ref(vals, 2)
    np.testing.assert_allclose(np.asarray(got),
                               [0.0, -8.0, 0, 0, 0, 0, 6.0, 0])


def test_ops_mode_dispatch():
    """mode knob semantics off-TPU: "off"/"auto" -> oracle, "on" ->
    interpret kernel; force_kernel stays an alias for "on"."""
    assert ops.resolve("off") == "oracle"
    assert ops.resolve("auto") == "oracle"  # CPU container
    assert ops.resolve("on") == "interpret"
    assert ops.resolve("auto", force_kernel=True) == "interpret"
    with pytest.raises(ValueError, match="kernels mode"):
        ops.resolve("sometimes")
    x = jax.random.normal(jax.random.PRNGKey(0), (257,))
    key = jax.random.PRNGKey(1)
    for mode in ("auto", "on", "off"):
        assert bool(jnp.all(ops.int8_roundtrip(x, key, mode=mode)
                            == ops.int8_roundtrip(x, key, mode="off")))
        assert bool(jnp.all(ops.topk_select(x, 31, mode=mode)
                            == ops.topk_select(x, 31, mode="off")))


def test_gram_kernel_feeds_lbfgs_identically():
    """End-to-end: a direction computed from the kernel Gram equals the
    pure-jnp one (the optimizer consumes either interchangeably)."""
    from repro.core import lbfgs
    rng = np.random.default_rng(0)
    m, d = 4, 200
    params = {"w": jnp.zeros(d)}
    h = lbfgs.init(params, m)
    for _ in range(m):
        s = rng.normal(size=d)
        h = lbfgs.push(h, {"w": jnp.asarray(s)},
                       {"w": jnp.asarray(s * rng.uniform(0.5, 2, d))})
    g = {"w": jnp.asarray(rng.normal(size=d))}
    basis = jnp.concatenate([
        np.asarray(h.s["w"]), np.asarray(h.y["w"]), np.asarray(g["w"])[None]
    ], axis=0)
    M_kernel = ops.vlbfgs_gram(basis, force_kernel=True)
    M_ref = lbfgs.gram_matrix(h, g)
    np.testing.assert_allclose(np.asarray(M_kernel), np.asarray(M_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------- convergence fingerprint
def test_fim_lbfgs_convergence_fingerprint_invariant_under_kernels():
    """Routing the client Fisher diagonal and the server Gram matrix
    through the Pallas ops must not move the optimizer's trajectory:
    kernels="on" (interpret kernels everywhere) and kernels="off" (the
    historical pure-jnp path) produce the same iterates to f32 tolerance
    on a deterministic quadratic."""
    from repro.core import fim_lbfgs
    from repro.fed import client as fed_client

    rng = np.random.default_rng(7)
    d = 300
    target = jnp.asarray(rng.normal(size=d).astype(np.float32))
    curv = jnp.asarray(rng.uniform(0.5, 2.0, size=d).astype(np.float32))

    def loss_fn(params, batch):
        r = (params["w"] - target) * batch["x"][:, None]
        return jnp.mean(jnp.sum(curv * r * r, axis=1))

    def per_example_loss(params, x, y):
        r = (params["w"] - target) * x
        return jnp.sum(curv * r * r)

    batch = {"x": jnp.ones((8,)), "y": jnp.zeros((8,), jnp.int32)}

    def run(kernels: str):
        grad_fim = fed_client.make_grad_fim_fn(
            loss_fn, per_example_loss, "per_example", kernels=kernels)
        cfg = fim_lbfgs.FimLbfgsConfig(learning_rate=0.3, m=4,
                                       kernels=kernels)
        params = {"w": jnp.zeros((d,), jnp.float32)}
        state = fim_lbfgs.init(params, cfg)
        losses = []
        for _ in range(8):
            g, diag, loss = grad_fim(params, batch)
            params, state, _ = fim_lbfgs.update(state, params, g, diag, cfg)
            losses.append(float(loss))
        return params, losses

    p_off, l_off = run("off")
    p_on, l_on = run("on")
    assert l_off[-1] < l_off[0]  # it actually converges
    np.testing.assert_allclose(l_on, l_off, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(p_on["w"]), np.asarray(p_off["w"]),
                               rtol=1e-4, atol=1e-5)
