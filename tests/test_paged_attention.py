"""Kernel-level differential gate for the paged decode tier.

Two kernels, each pinned against an unfused dense oracle:

* ``kernels.paged_attention`` (Pallas, scalar-prefetched block tables) vs
  ``kernels.ref.paged_attention`` (gather-everything masked softmax) over
  ragged context lengths, block sizes, GQA group sizes, sliding windows
  and logit softcaps — plus an end-to-end check against the model's jnp
  paged-decode attention path;
* ``kernels.fused_bma_select`` vs ``kernels.ref.bma_select`` AND the
  engine's unfused ``mixture_logprobs`` + ``select_tokens`` composition —
  token draws must be BIT-identical (Gumbel-argmax identity, same key).

Everything runs in interpret mode on CPU; the same code compiles on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels import ref
from repro.serve.engine.bma import mixture_logprobs
from repro.serve.sampling import SamplingParams, select_tokens


def _paged_case(key, *, B, Hkv, G, d, bs, M, ragged=True):
    """Random pool + tables: each sequence owns its first rows' pages."""
    kq, kk, kv, kc = jax.random.split(key, 4)
    num_pages = B * M + 1
    q = jax.random.normal(kq, (B, Hkv, G, d), jnp.float32)
    k_pages = jax.random.normal(kk, (num_pages, bs, Hkv, d), jnp.float32)
    v_pages = jax.random.normal(kv, (num_pages, bs, Hkv, d), jnp.float32)
    tables = (1 + jnp.arange(B * M, dtype=jnp.int32)).reshape(B, M)
    if ragged:
        ctx = jax.random.randint(kc, (B,), 0, M * bs)
    else:
        ctx = jnp.full((B,), M * bs - 1, jnp.int32)
    return q, k_pages, v_pages, tables, ctx


def _live_reference(q, k, v, tab, ctx, **kw):
    """The reference for live sequences (ctx >= 0); zero for the others."""
    want = ref.paged_attention(q, k, v, tab, jnp.maximum(ctx, 0), **kw)
    return jnp.where((ctx >= 0)[:, None, None, None], want, 0.0)


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("bs", [8, 16, 64])
    def test_matches_dense_reference_across_block_sizes(self, bs):
        q, k, v, tab, ctx = _paged_case(
            jax.random.PRNGKey(bs), B=3, Hkv=2, G=2, d=16, bs=bs, M=3
        )
        got = kernels.paged_attention(q, k, v, tab, ctx)
        want = ref.paged_attention(q, k, v, tab, ctx)
        np.testing.assert_allclose(got, want, atol=2e-6)

    # M = 7 pages a row, bs = 8: contexts ending mid-page, on the last
    # position of a page, on the first of the next, on the table's last
    # entry; -1 marks a done or free sequence (no pages, zero output)
    @pytest.mark.parametrize("ctx", [
        (-1, 20, -1, 3, -1),
        (11, 15, 16, 0, 7),
        (55, 55, 47, 48, 8),
        (-1, -1, -1, -1, -1),
    ], ids=["dead-beside-live", "mid-page-and-boundaries", "last-entry", "all-dead"])
    @pytest.mark.parametrize("pps", [1, 3, 7], ids=lambda n: f"pps{n}")
    def test_context_edges_and_chunking(self, monkeypatch, ctx, pps):
        """Pages per chunk that do and do not divide each sequence's live
        pages (pps 3 over 1..7 pages), one page a chunk, a whole row a
        chunk, on a permuted table."""
        import importlib

        pa = importlib.import_module("repro.kernels.paged_attention")
        B, Hkv, G, d, bs, M = 5, 2, 2, 16, 8, 7
        q, k, v, _, _ = _paged_case(
            jax.random.PRNGKey(pps), B=B, Hkv=Hkv, G=G, d=d, bs=bs, M=M
        )
        perm = 1 + np.random.default_rng(pps).permutation(B * M)
        tab = jnp.asarray(perm.reshape(B, M), jnp.int32)
        ctx = jnp.asarray(ctx, jnp.int32)
        page_bytes = bs * Hkv * d * k.dtype.itemsize
        monkeypatch.setattr(pa, "KV_VMEM_BYTES", 4 * page_bytes * pps)
        assert pa.pages_per_step(bs, Hkv * d * k.dtype.itemsize, M) == pps
        got = pa.paged_attention(q, k, v, tab, ctx)
        np.testing.assert_allclose(got, _live_reference(q, k, v, tab, ctx), atol=2e-6)

    def test_vmap_over_members_shares_tables(self):
        """How the engine calls it: vmapped over K=2 members, queries and
        pools batched, tables and contexts shared — one kernel with the
        members on its grid, each member its own reference."""
        q, k, v, tab, ctx = _paged_case(
            jax.random.PRNGKey(11), B=4, Hkv=2, G=2, d=16, bs=8, M=4
        )
        ctx = ctx.at[2].set(-1)
        qs, ks, vs = (jnp.stack([x, -0.5 * x]) for x in (q, k, v))
        fn = jax.vmap(lambda a, b, c: kernels.paged_attention(a, b, c, tab, ctx))
        jaxpr = str(jax.make_jaxpr(fn)(qs, ks, vs))
        assert jaxpr.count("pallas_call") == 1 and "GridMapping(grid=(2,)" in jaxpr
        got = fn(qs, ks, vs)
        for m in range(2):
            np.testing.assert_allclose(
                got[m], _live_reference(qs[m], ks[m], vs[m], tab, ctx), atol=2e-6
            )
        # contexts that differ per member: one kernel call per member
        ctxs = jnp.stack([ctx, ctx[::-1]])
        got = jax.vmap(kernels.paged_attention, in_axes=(0, 0, 0, None, 0))(qs, ks, vs, tab, ctxs)
        for m in range(2):
            np.testing.assert_allclose(
                got[m], _live_reference(qs[m], ks[m], vs[m], tab, ctxs[m]), atol=2e-6
            )

    @pytest.mark.parametrize(
        "B,Hkv,G,d,bs,M",
        [(1, 1, 1, 16, 8, 1), (2, 1, 4, 32, 16, 3), (4, 2, 1, 16, 8, 6),
         (2, 2, 2, 64, 8, 4)],
    )
    def test_shapes_grid(self, B, Hkv, G, d, bs, M):
        q, k, v, tab, ctx = _paged_case(
            jax.random.PRNGKey(B * 100 + d), B=B, Hkv=Hkv, G=G, d=d, bs=bs, M=M
        )
        got = kernels.paged_attention(q, k, v, tab, ctx)
        want = ref.paged_attention(q, k, v, tab, ctx)
        np.testing.assert_allclose(got, want, atol=2e-6)

    def test_window_and_softcap(self):
        q, k, v, tab, ctx = _paged_case(
            jax.random.PRNGKey(7), B=4, Hkv=2, G=1, d=16, bs=8, M=6
        )
        for kw in ({"window": 12}, {"softcap": 20.0}, {"window": 5, "softcap": 8.0}):
            got = kernels.paged_attention(q, k, v, tab, ctx, **kw)
            want = ref.paged_attention(q, k, v, tab, ctx, **kw)
            np.testing.assert_allclose(got, want, atol=2e-6, err_msg=str(kw))

    def test_custom_scale(self):
        q, k, v, tab, ctx = _paged_case(
            jax.random.PRNGKey(9), B=2, Hkv=1, G=2, d=16, bs=8, M=2
        )
        got = kernels.paged_attention(q, k, v, tab, ctx, scale=0.5)
        want = ref.paged_attention(q, k, v, tab, ctx, scale=0.5)
        np.testing.assert_allclose(got, want, atol=2e-6)

    def test_ctx_zero_attends_only_position_zero(self):
        """Inclusive-position convention: ctx = 0 means exactly one valid
        key — the reference degenerates to v[page0, 0]."""
        q, k, v, tab, _ = _paged_case(
            jax.random.PRNGKey(3), B=2, Hkv=1, G=1, d=16, bs=8, M=2
        )
        ctx = jnp.zeros((2,), jnp.int32)
        got = kernels.paged_attention(q, k, v, tab, ctx)
        want = v[tab[:, 0], 0][:, :, None, :]  # softmax over one key
        np.testing.assert_allclose(got, want, atol=2e-6)

    def test_table_permutation_invariance(self):
        """Physical page placement is immaterial: permuting the pool and
        rewriting tables to match leaves the output unchanged."""
        q, k, v, tab, ctx = _paged_case(
            jax.random.PRNGKey(5), B=2, Hkv=1, G=2, d=16, bs=8, M=3
        )
        base = kernels.paged_attention(q, k, v, tab, ctx)
        perm = np.r_[0, 1 + np.random.default_rng(0).permutation(k.shape[0] - 1)]
        inv = np.argsort(perm)
        got = kernels.paged_attention(
            q, k[perm], v[perm], jnp.asarray(inv)[tab], ctx
        )
        np.testing.assert_allclose(got, base, atol=1e-6)

    def test_matches_model_jnp_paged_path(self):
        """The kernel and the model's pure-jnp gather path (what CPU serving
        uses) agree — the same pin the engine differential relies on."""
        from repro import configs
        from repro.models import get_model, init_params
        from repro.models import layers as L

        cfg = configs.get_config("qwen3-0.6b", smoke=True).replace(
            vocab_size=32, d_model=32, num_layers=1, num_heads=2,
            num_kv_heads=1, head_dim=16, d_ff=32,
        )
        model = get_model(cfg)
        params = init_params(model.param_specs(cfg), jax.random.PRNGKey(0))
        prompt = jnp.arange(1, 7, dtype=jnp.int32)[None]
        _, cache = model.prefill(cfg, params, {"tokens": prompt}, 16, None)
        pools = model.paged.make_pools(cfg, 5, 8, cfg.compute_dtype)
        tab = jnp.asarray([[1, 2]], jnp.int32)
        pools = model.paged.prefill_write(cfg, pools, cache, tab[0], 8)
        tok = jnp.asarray([[3]], jnp.int32)
        ctx = jnp.asarray([6], jnp.int32)
        wb = tab[:, 0]
        jnp_logits, _ = model.paged.decode_step(
            cfg, params, pools, tok, tab, ctx, wb
        )
        kcfg = cfg.replace(use_flash_kernel=True)
        k_logits, _ = model.paged.decode_step(
            kcfg, params, pools, tok, tab, ctx, wb
        )
        np.testing.assert_allclose(k_logits, jnp_logits, atol=2e-5)


class TestFusedBmaSelect:
    def _logits(self, key, K=3, S=4, V=40):
        return 4.0 * jax.random.normal(key, (K, S, V), jnp.float32)

    @pytest.mark.parametrize("mode", ["probs", "logprobs"])
    @pytest.mark.parametrize("temperature,top_k",
                             [(0.0, 0), (1.3, 0), (0.7, 5), (2.0, 1)])
    def test_matches_ref_oracle(self, mode, temperature, top_k):
        logits = self._logits(jax.random.PRNGKey(1))
        key = jax.random.PRNGKey(2)
        S, V = logits.shape[1:]
        gumbel = (jax.random.gumbel(key, (S, V), jnp.float32)
                  if temperature > 0 else jnp.zeros((S, V), jnp.float32))
        tok, logp = kernels.fused_bma_select(
            logits, key, mode=mode, temperature=temperature, top_k=top_k
        )
        rtok, rlogp = ref.bma_select(
            logits, gumbel, mode=mode, temperature=temperature, top_k=top_k
        )
        np.testing.assert_array_equal(tok, rtok)
        np.testing.assert_allclose(logp, rlogp, atol=2e-6)

    @pytest.mark.parametrize("mode", ["probs", "logprobs"])
    @pytest.mark.parametrize("temperature,top_k",
                             [(0.0, 0), (1.3, 0), (0.7, 5)])
    def test_tokens_bit_equal_to_engine_path(self, mode, temperature, top_k):
        """The exact composition the engine would otherwise run — including
        jax.random.categorical with the SAME key — must pick the SAME
        tokens (Gumbel-argmax identity)."""
        logits = self._logits(jax.random.PRNGKey(3))
        key = jax.random.PRNGKey(4)
        tok, logp = kernels.fused_bma_select(
            logits, key, mode=mode, temperature=temperature, top_k=top_k
        )
        want_logp = mixture_logprobs(logits, mode)
        want_tok = select_tokens(
            want_logp, key, SamplingParams(temperature=temperature, top_k=top_k)
        )
        np.testing.assert_array_equal(tok, want_tok)
        np.testing.assert_allclose(logp, want_logp, atol=2e-6)

    def test_top_k_tie_handling_matches_mask(self):
        """Ties at the k-th value keep every tied candidate, exactly like
        sampling._top_k_mask (strictly-less threshold)."""
        row = jnp.asarray([[2.0, 2.0, 1.0, 0.0, 2.0, -1.0]], jnp.float32)
        logits = jnp.log(jax.nn.softmax(row))[None]  # K=1: mixture == row
        gumbel = jnp.zeros((1, 6), jnp.float32)
        tok, _ = ref.bma_select(logits, gumbel, mode="probs",
                                temperature=1.0, top_k=2)
        ftok, _ = kernels.fused_bma_select(
            logits, jax.random.PRNGKey(0), mode="probs",
            temperature=1e9, top_k=2,  # huge T: selection ~ mask + zero noise
        )
        assert int(tok[0]) == 0  # first of the tied maxima
        assert int(ftok[0]) in (0, 1, 4)  # any tied-survivor is admissible

    def test_greedy_single_member_is_argmax(self):
        logits = self._logits(jax.random.PRNGKey(6), K=1)
        tok, _ = kernels.fused_bma_select(logits, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(tok, jnp.argmax(logits[0], axis=-1))
