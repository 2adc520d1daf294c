"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.gather_agg.ops import gather_agg, resolve_agg_impl
from repro.kernels.gather_agg.ref import gather_agg_ref
from repro.kernels.gather_mean.ops import gather_mean
from repro.kernels.gather_mean.ref import gather_mean_ref
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import moe_gmm_ref
from repro.kernels.rwkv6_chunk.ops import wkv6_op
from repro.kernels.rwkv6_chunk.ref import wkv6_ref
from repro.models.lm.attention import attention_ref, flash_attention
from repro.models.lm.rwkv6 import wkv6_chunked, wkv6_scan


# ---------------------------------------------------------------------------
# gather_agg (fused gather + weighted reduce, custom VJP)
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([16, 50, 200]), d=st.sampled_from([5, 17, 64]),
       r=st.sampled_from([1, 4, 10]), f=st.sampled_from([8, 128, 96]),
       bd=st.sampled_from([1, 4, 8]), seed=st.integers(0, 20))
def test_gather_agg_matches_ref(n, d, r, f, bd, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (n, f), jnp.float32)
    idx = jax.random.randint(ks[1], (d, r), 0, n)
    w = jax.random.normal(ks[2], (d, r), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(gather_agg(x, idx, w, impl="pallas", block_dst=bd)),
        np.asarray(gather_agg_ref(x, idx, w)), rtol=1e-5, atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(n=st.sampled_from([20, 80]), d=st.sampled_from([7, 33]),
       r=st.sampled_from([3, 6]), f=st.sampled_from([16, 64]),
       seed=st.integers(0, 20))
def test_gather_agg_grads_match_ref(n, d, r, f, seed):
    """Backward Pallas pair (scatter-add dx, gather-dot dw) vs autodiff of
    the jnp oracle."""
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (n, f), jnp.float32)
    idx = jax.random.randint(ks[1], (d, r), 0, n)
    w = jax.random.normal(ks[2], (d, r), jnp.float32)
    cot = jax.random.normal(ks[3], (d, f), jnp.float32)

    def loss(impl):
        return jax.grad(
            lambda x, w: (gather_agg(x, idx, w, impl=impl) * cot).sum(),
            argnums=(0, 1))(x, w)

    (dxp, dwp), (dxj, dwj) = loss("pallas"), loss("jnp")
    np.testing.assert_allclose(np.asarray(dxp), np.asarray(dxj),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dwp), np.asarray(dwj),
                               rtol=1e-4, atol=1e-4)


def test_gather_agg_repeated_rows_scatter_add():
    """Many edges hitting the SAME source row must accumulate, not race."""
    x = jnp.ones((4, 32))
    idx = jnp.zeros((6, 5), jnp.int32)            # every edge -> row 0
    w = jnp.ones((6, 5))
    cot = jnp.ones((6, 32))
    dx = jax.grad(lambda x: (gather_agg(x, idx, w, impl="pallas")
                             * cot).sum())(x)
    assert float(dx[0, 0]) == 30.0                # 6 dst x 5 slots
    assert float(jnp.abs(dx[1:]).max()) == 0.0    # untouched rows stay zero


def test_gather_agg_kernels_named_in_op_metadata():
    """The forward and both backward kernels keep their scopes through the
    custom VJP: a device trace finds each under its own name."""
    import re
    x = jnp.ones((40, 16), jnp.float32)
    idx = jnp.zeros((24, 4), jnp.int32)
    w = jnp.ones((24, 4), jnp.float32)

    def loss(x, w):
        return jnp.sum(gather_agg(x, idx, w, impl="pallas") ** 2)
    txt = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w) \
        .compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', txt))
    for kernel in ("gather_agg_fwd", "gather_agg_dx", "gather_agg_dw"):
        assert any(f"/{kernel}/" in n for n in names), kernel
    # the backward kernels sit under the transpose of the forward
    assert any(re.search(r"transpose\(.*\)/gather_agg_dx/", n)
               for n in names)


@pytest.mark.parametrize("f", [47])
def test_gather_agg_class_width_fwd_bwd_matches_jnp(f):
    """Forward, dx and dw at a width that is no multiple of 8 (`gat_res`'s
    last layer: 4 heads of 47 classes folded into rows), Pallas in
    interpret mode vs the jnp path."""
    H, n, d, r = 4, 30, 21, 10
    ks = jax.random.split(jax.random.key(47), 5)
    x = jax.random.normal(ks[0], (n * H, f), jnp.float32)
    src = jax.random.randint(ks[1], (d, r), 0, n)
    idx = (src[:, None, :] * H + jnp.arange(H)[None, :, None]).reshape(
        d * H, r)
    w = jax.nn.softmax(jax.random.normal(ks[2], (d * H, r)), axis=1)
    cot = jax.random.normal(ks[3], (d * H, f), jnp.float32)

    def run(impl):
        out, vjp = jax.vjp(lambda x, w: gather_agg(x, idx, w, impl=impl),
                           x, w)
        return (out,) + vjp(cot)

    for got, want in zip(run("pallas"), run("jnp")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows_per_pass", [8, 16])
def test_gather_agg_dx_multi_pass_matches_ref(monkeypatch, rows_per_pass):
    """dx split into several source-row passes (large n_src * F on the
    chip) vs autodiff of the jnp oracle: every source row is hit, so edges
    fall on both sides of every pass boundary."""
    from repro.kernels.gather_agg import kernel
    n, d, r, f = 50, 33, 6, 16
    # a 16-wide row is lane-padded to 128 float32 in the dx block
    monkeypatch.setattr(kernel, "_DX_BYTES", rows_per_pass * 128 * 4)
    assert kernel.dx_block_rows(n, f) == rows_per_pass < n
    rng = np.random.default_rng(rows_per_pass)
    idx = jnp.asarray(rng.permutation(np.arange(d * r) % n).reshape(d, r),
                      jnp.int32)
    w = jnp.asarray(rng.normal(size=(d, r)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(d, f)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)

    def dx(impl):
        return jax.grad(lambda x: (gather_agg(x, idx, w, impl=impl)
                                   * cot).sum())(x)

    np.testing.assert_allclose(np.asarray(dx("pallas")),
                               np.asarray(dx("jnp")), rtol=1e-4, atol=1e-4)


def test_resolve_agg_impl():
    assert resolve_agg_impl("jnp") == "jnp"
    assert resolve_agg_impl("pallas") == "pallas"
    # this suite runs on CPU (conftest pins the platform)
    assert resolve_agg_impl("auto") == "jnp"
    with pytest.raises(ValueError):
        resolve_agg_impl("nope")


# ---------------------------------------------------------------------------
# gather_mean (deprecated shim over gather_agg)
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(n=st.sampled_from([16, 50, 200]), d=st.sampled_from([8, 33]),
       r=st.sampled_from([1, 4, 10]),
       f=st.sampled_from([128, 256, 96]),
       dense=st.booleans(), seed=st.integers(0, 20))
def test_gather_mean_matches_ref(n, d, r, f, dense, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (n, f), jnp.float32)
    idx = jax.random.randint(ks[1], (d, r), 0, n)
    mask = jnp.ones((d, r), bool) if dense else \
        jax.random.bernoulli(ks[2], 0.7, (d, r))
    np.testing.assert_allclose(
        np.asarray(gather_mean(x, idx, mask)),
        np.asarray(gather_mean_ref(x, idx, mask)), rtol=1e-5, atol=1e-5)


def test_gather_mean_all_masked_row_is_zero():
    x = jnp.ones((8, 128))
    idx = jnp.zeros((2, 4), jnp.int32)
    mask = jnp.zeros((2, 4), bool)
    assert float(jnp.abs(gather_mean(x, idx, mask)).max()) == 0.0


# ---------------------------------------------------------------------------
# flash attention (kernel + custom-vjp jnp twin)
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(b=st.sampled_from([1, 2]), s=st.sampled_from([32, 64]),
       h=st.sampled_from([2, 4]), g=st.sampled_from([1, 2]),
       d=st.sampled_from([16, 32]),
       causal=st.booleans(),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 20))
def test_flash_kernel_matches_ref(b, s, h, g, d, causal, dtype, seed):
    kh = h // g
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d)).astype(dtype)
    out = flash_attention_op(q, k, v, causal=causal, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_kernel_sliding_window():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    out = flash_attention_op(q, k, v, causal=True, window=16,
                             is_global=False, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=True, window=16, is_global=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_custom_vjp_grads_match_ref():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    f = lambda q, k, v: (flash_attention(q, k, v, chunk=16) ** 2).sum()
    r = lambda q, k, v: (attention_ref(q, k, v) ** 2).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(b=st.sampled_from([1, 2]), t=st.sampled_from([16, 64]),
       h=st.sampled_from([1, 2]), n=st.sampled_from([8, 16]),
       seed=st.integers(0, 20))
def test_wkv6_kernel_matches_scan(b, t, h, n, seed):
    ks = jax.random.split(jax.random.key(seed), 5)
    r = jax.random.normal(ks[0], (b, t, h, n))
    k = jax.random.normal(ks[1], (b, t, h, n))
    v = jax.random.normal(ks[2], (b, t, h, n))
    logw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (b, t, h, n)) * 0.5),
                    -5.0, -1e-4)
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    out = wkv6_op(r, k, v, logw, u)
    ref = wkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_chunked_matches_scan_with_state():
    ks = jax.random.split(jax.random.key(9), 5)
    B, T, H, N = 2, 48, 2, 16
    r = jax.random.normal(ks[0], (B, T, H, N))
    k = jax.random.normal(ks[1], (B, T, H, N))
    v = jax.random.normal(ks[2], (B, T, H, N))
    logw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (B, T, H, N))),
                    -5.0, -1e-4)
    u = jax.random.normal(ks[4], (H, N)) * 0.1
    s0 = jax.random.normal(jax.random.key(10), (B, H, N, N)) * 0.1
    oc, sc = wkv6_chunked(r, k, v, logw, u, s0, chunk=16)
    os_, ss = wkv6_scan(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(os_),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(ss),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# moe gmm
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(e=st.sampled_from([1, 4]), c=st.sampled_from([128, 256]),
       d=st.sampled_from([128, 256]), f=st.sampled_from([128, 384]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 10))
def test_moe_gmm_matches_ref(e, c, d, f, dtype, seed):
    ks = jax.random.split(jax.random.key(seed), 2)
    x = jax.random.normal(ks[0], (e, c, d)).astype(dtype)
    w = jax.random.normal(ks[1], (e, d, f)).astype(dtype)
    out = moe_gmm(x, w)
    ref = moe_gmm_ref(x, w)
    tol = 2e-1 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# model aggregation: agg_impl="pallas" vs agg_impl="jnp" (fwd + bwd)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("model", ["sage", "gcn", "gat", "gat_res"])
def test_apply_gnn_pallas_matches_jnp(tiny_graph, model):
    import dataclasses

    from repro.configs.base import GNNConfig
    from repro.core import minibatch as mb
    from repro.graphs.csr import DeviceGraph
    from repro.models.gnn.models import apply_gnn, init_gnn
    from repro.train.losses import gnn_softmax_ce

    g = tiny_graph
    gdev = DeviceGraph.from_graph(g)
    feats = jnp.asarray(g.features)
    cfg_j = GNNConfig("t", model, 2, 32, g.feat_dim, g.num_classes,
                      fanout=(4, 4), dropout=0.0, agg_impl="jnp")
    cfg_p = dataclasses.replace(cfg_j, agg_impl="pallas")
    params = init_gnn(cfg_j, jax.random.key(1))
    batch = mb.build_batch(jax.random.key(2), gdev,
                           jnp.asarray(g.train_ids[:32], jnp.int32),
                           jnp.asarray(g.labels), (4, 4), (256, 384), 0.9)

    out_j = apply_gnn(cfg_j, params, batch, feats, gdev.degrees,
                      feats_global=True)
    out_p = apply_gnn(cfg_p, params, batch, feats, gdev.degrees,
                      feats_global=True)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                               rtol=1e-5, atol=1e-5)

    def loss(p, cfg):
        lg = apply_gnn(cfg, p, batch, feats, gdev.degrees,
                       feats_global=True)
        return gnn_softmax_ce(lg, batch.labels,
                              batch.label_mask.astype(jnp.float32))

    gj = jax.grad(loss)(params, cfg_j)
    gp = jax.grad(loss)(params, cfg_p)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gj)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
