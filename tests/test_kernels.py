"""Per-kernel allclose vs pure-jnp oracles: shape/dtype sweeps (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.chunk_score.kernel import chunk_score
from repro.kernels.chunk_score.ref import chunk_score_ref
from repro.kernels.chunk_attention.kernel import chunk_attention
from repro.kernels.chunk_attention.ref import chunk_attention_ref
from repro.kernels.chunk_attention.ops import reprefill_attention_paged
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("nq,nkv,s,d", [(4, 2, 128, 64), (8, 8, 256, 128), (2, 1, 64, 32)])
    def test_causal_matches_ref(self, dtype, nq, nkv, s, d):
        q = _rand(0, (2, nq, s, d), dtype)
        k = _rand(1, (2, nkv, s, d), dtype)
        v = _rand(2, (2, nkv, s, d), dtype)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **_tol(dtype))

    def test_sliding_window(self):
        q = _rand(0, (1, 4, 128, 64), jnp.float32)
        k = _rand(1, (1, 2, 128, 64), jnp.float32)
        v = _rand(2, (1, 2, 128, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=32, block_q=32,
                              block_k=32, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True, window=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    @given(
        s_pow=st.integers(6, 8),
        d=st.sampled_from([32, 64, 128]),
        heads=st.sampled_from([(4, 4), (4, 2), (8, 1)]),
    )
    @settings(max_examples=8, deadline=None)
    def test_shape_sweep(self, s_pow, d, heads):
        nq, nkv = heads
        s = 2 ** s_pow
        q = _rand(3, (1, nq, s, d), jnp.float32)
        k = _rand(4, (1, nkv, s, d), jnp.float32)
        v = _rand(5, (1, nkv, s, d), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


class TestChunkScore:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, dtype):
        q = _rand(0, (8, 32, 64), dtype)
        k = _rand(1, (2, 512, 64), dtype)
        got = chunk_score(q, k, 16, block_k=128, interpret=True)
        ref = chunk_score_ref(q, k, 16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref, np.float32),
                                   **_tol(dtype))

    def test_scores_sum_to_total_mass(self):
        q = _rand(2, (4, 16, 32), jnp.float32)
        k = _rand(3, (2, 256, 32), jnp.float32)
        got = chunk_score(q, k, 16, block_k=64, interpret=True)
        np.testing.assert_allclose(float(got.sum()), 4 * 16, rtol=1e-4)

    @given(c=st.sampled_from([8, 16, 32]), nkb=st.integers(2, 4))
    @settings(max_examples=6, deadline=None)
    def test_chunk_size_sweep(self, c, nkb):
        n = 128 * nkb
        q = _rand(4, (4, 16, 64), jnp.float32)
        k = _rand(5, (4, n, 64), jnp.float32)
        got = chunk_score(q, k, c, block_k=128, interpret=True)
        ref = chunk_score_ref(q, k, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5)


class TestChunkAttention:
    def test_partials_match_ref(self):
        q = _rand(0, (8, 32, 64), jnp.float32)
        k_pool = _rand(1, (32, 16, 2, 64), jnp.float32)
        v_pool = _rand(2, (32, 16, 2, 64), jnp.float32)
        idx = jnp.array([3, 7, 1, 30, 12, 0, 0, 0], jnp.int32)
        out_k, m_k, l_k, _ = chunk_attention(q, k_pool, v_pool, idx, 5, interpret=True)
        out_r, m_r, l_r, _ = chunk_attention_ref(q, k_pool, v_pool, idx, 5)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), rtol=1e-5, atol=1e-6)

    def test_full_selection_equals_dense(self):
        """budget=100%: merged prefix+suffix attention == dense oracle."""
        nq, nkv, s, d, m, c = 4, 2, 32, 64, 16, 16
        q = _rand(0, (nq, s, d), jnp.float32)
        k_pool = _rand(1, (m, c, nkv, d), jnp.float32)
        v_pool = _rand(2, (m, c, nkv, d), jnp.float32)
        k_suf = _rand(3, (s, nkv, d), jnp.float32)
        v_suf = _rand(4, (s, nkv, d), jnp.float32)
        idx = jnp.arange(m, dtype=jnp.int32)
        out, mass = reprefill_attention_paged(q, k_pool, v_pool, idx, m,
                                              k_suf, v_suf, use_kernel=True)
        # dense oracle
        group = nq // nkv
        kp = k_pool.reshape(m * c, nkv, d)
        vp = v_pool.reshape(m * c, nkv, d)
        k_all = jnp.concatenate([kp, k_suf])
        v_all = jnp.concatenate([vp, v_suf])
        qg = q.reshape(nkv, group, s, d)
        logits = jnp.einsum("ngsd,tnd->ngst", qg, k_all) * (d ** -0.5)
        mask = jnp.concatenate(
            [jnp.ones((s, m * c), bool),
             jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]], axis=1)
        logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, -1)
        oracle = jnp.einsum("ngst,tnd->ngsd", p, v_all).reshape(nq, s, d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(mass.sum()), nq, rtol=1e-3)

    def test_mass_normalized_per_head(self):
        q = _rand(0, (4, 16, 32), jnp.float32)
        k_pool = _rand(1, (8, 16, 2, 32), jnp.float32)
        v_pool = _rand(2, (8, 16, 2, 32), jnp.float32)
        idx = jnp.array([0, 3, 5, 7], jnp.int32)
        _, _, _, mass_raw = chunk_attention(q, k_pool, v_pool, idx, 4, interpret=True)
        _, _, _, mass_ref = chunk_attention_ref(q, k_pool, v_pool, idx, 4)
        denom = jnp.maximum(mass_raw.sum(-1, keepdims=True), 1e-30)
        got = (mass_raw / denom).sum(0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(mass_ref),
                                   rtol=1e-4, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_paged_decode_matches_ref(self, dtype):
        b, nq, nkv, d, page, n_pages, n_act = 2, 8, 2, 64, 16, 24, 6
        q = _rand(0, (b, nq, d), dtype)
        kp = _rand(1, (b, n_pages, page, nkv, d), dtype)
        vp = _rand(2, (b, n_pages, page, nkv, d), dtype)
        tbl = jnp.stack([
            jax.random.permutation(jax.random.PRNGKey(9), n_pages)[:n_act],
            jax.random.permutation(jax.random.PRNGKey(10), n_pages)[:n_act],
        ]).astype(jnp.int32)
        lens = jnp.array([n_act * page - 3, n_act * page - 17], jnp.int32)
        got, mass_g = decode_attention(q, kp, vp, tbl, lens, interpret=True)
        ref, mass_r = decode_attention_ref(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), **_tol(dtype))
        # per-page mass matches the oracle and normalizes per head
        np.testing.assert_allclose(np.asarray(mass_g), np.asarray(mass_r),
                                   rtol=5e-3, atol=5e-4)
        np.testing.assert_allclose(np.asarray(mass_g.sum(-1)),
                                   np.ones((b, nq)), rtol=1e-3)

    # ragged-batch case table: per request (n_res resident pages, t_tail
    # tail tokens); rows with fewer active pages pad their table with -1
    RAGGED_CASES = [
        # b=1 degenerate cases (the serving batch former's lone-plan path)
        ("b1_partial_tail", 8, [(3, 5)]),
        ("b1_exact_page", 8, [(2, 8)]),
        # ragged b=2: second request needs pad pages
        ("b2_ragged", 8, [(3, 5), (1, 17)]),
        # b=3: a request with no resident pages, tails crossing boundaries
        ("b3_no_resident", 4, [(0, 4), (5, 1), (2, 9)]),
        # mostly-pad row next to an exact fill
        ("b2_mostly_pad", 16, [(2, 16), (0, 3)]),
    ]

    @pytest.mark.parametrize("name,page,reqs",
                             RAGGED_CASES, ids=[c[0] for c in RAGGED_CASES])
    def test_ragged_batch_matches_ref(self, name, page, reqs):
        """Kernel == oracle on ragged batches; pad slots (table -1) carry
        exactly zero mass while valid pages' mass sums to ~1 per head."""
        nq, nkv, d = 4, 2, 32
        b = len(reqs)
        n_active = [n_res + -(-t // page) for n_res, t in reqs]
        width = max(n_active)
        n_pages = width + 2  # physical pool larger than any table row
        q = _rand(0, (b, nq, d), jnp.float32)
        kp = _rand(1, (b, n_pages, page, nkv, d), jnp.float32)
        vp = _rand(2, (b, n_pages, page, nkv, d), jnp.float32)
        tbl = np.full((b, width), -1, np.int32)
        lens = np.zeros(b, np.int32)
        for i, (n_res, t) in enumerate(reqs):
            tbl[i, : n_active[i]] = np.arange(n_active[i])
            lens[i] = n_res * page + t
        tbl, lens = jnp.asarray(tbl), jnp.asarray(lens)
        got, mass_g = decode_attention(q, kp, vp, tbl, lens, interpret=True)
        ref, mass_r = decode_attention_ref(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(np.asarray(mass_g), np.asarray(mass_r),
                                   rtol=3e-4, atol=3e-5)
        mg = np.asarray(mass_g)
        for i in range(b):
            assert mg[i, :, n_active[i]:].max(initial=0.0) == 0.0, (
                f"{name}: pad pages of request {i} carry mass")
            np.testing.assert_allclose(mg[i, :, : n_active[i]].sum(-1),
                                       np.ones(nq), rtol=1e-3)

    def test_pad_slots_leave_valid_pages_bit_identical(self):
        """Widening a table with -1 slots must not perturb the real pages —
        the contract that lets TailPool keep a fixed-capacity table."""
        nq, nkv, d, page, n_pages, n_act = 4, 2, 32, 8, 8, 3
        q = _rand(0, (1, nq, d), jnp.float32)
        kp = _rand(1, (1, n_pages, page, nkv, d), jnp.float32)
        vp = _rand(2, (1, n_pages, page, nkv, d), jnp.float32)
        lens = jnp.array([n_act * page - 2], jnp.int32)
        tight = jnp.arange(n_act, dtype=jnp.int32)[None]
        wide = jnp.concatenate(
            [tight, jnp.full((1, 3), -1, jnp.int32)], axis=1)
        out_t, mass_t = decode_attention(q, kp, vp, tight, lens, interpret=True)
        out_w, mass_w = decode_attention(q, kp, vp, wide, lens, interpret=True)
        np.testing.assert_array_equal(np.asarray(out_t), np.asarray(out_w))
        np.testing.assert_array_equal(np.asarray(mass_t),
                                      np.asarray(mass_w)[:, :, :n_act])
        assert np.asarray(mass_w)[:, :, n_act:].max() == 0.0

    @given(n_act=st.integers(1, 8), valid_frac=st.floats(0.2, 1.0))
    @settings(max_examples=8, deadline=None)
    def test_length_mask_sweep(self, n_act, valid_frac):
        b, nq, nkv, d, page, n_pages = 1, 4, 4, 32, 8, 8
        q = _rand(0, (b, nq, d), jnp.float32)
        kp = _rand(1, (b, n_pages, page, nkv, d), jnp.float32)
        vp = _rand(2, (b, n_pages, page, nkv, d), jnp.float32)
        tbl = jnp.arange(n_act, dtype=jnp.int32)[None]
        lens = jnp.array([max(1, int(n_act * page * valid_frac))], jnp.int32)
        got, mass_g = decode_attention(q, kp, vp, tbl, lens, interpret=True)
        ref, mass_r = decode_attention_ref(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(np.asarray(mass_g), np.asarray(mass_r),
                                   rtol=3e-4, atol=3e-5)


class TestSelectiveScan:
    def test_matches_sequential_ref(self):
        from repro.kernels.selective_scan.kernel import selective_scan
        from repro.kernels.selective_scan.ref import selective_scan_ref
        b, s, d_in, n = 2, 64, 128, 8
        x = _rand(0, (b, s, d_in), jnp.float32)
        dt = jax.nn.softplus(_rand(1, (b, s), jnp.float32))
        A = -jnp.exp(_rand(2, (d_in, n), jnp.float32))
        B = _rand(3, (b, s, n), jnp.float32)
        C = _rand(4, (b, s, n), jnp.float32)
        y, h = selective_scan(x, dt, A, B, C, block_s=16, block_d=64,
                              interpret=True)
        yr, hr = selective_scan_ref(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-5, atol=1e-5)

    def test_matches_chunked_model_scan(self):
        """The model's chunked associative scan and the kernel agree."""
        from repro.kernels.selective_scan.kernel import selective_scan
        from repro.models.ssm import _selective_scan_chunked
        b, s, d_in, n = 1, 128, 64, 4
        x = _rand(5, (b, s, d_in), jnp.float32)
        dt_s = jax.nn.softplus(_rand(6, (b, s), jnp.float32))
        A = -jnp.exp(_rand(7, (d_in, n), jnp.float32))
        B = _rand(8, (b, s, n), jnp.float32)
        C = _rand(9, (b, s, n), jnp.float32)
        y_k, h_k = selective_scan(x, dt_s, A, B, C, block_s=32, block_d=32,
                                  interpret=True)
        dt_full = dt_s[..., None] * jnp.ones((d_in,), jnp.float32)
        y_c, h_c = _selective_scan_chunked(x, dt_full, A, B, C, chunk=32)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_c),
                                   rtol=1e-4, atol=1e-4)

    @given(bs=st.sampled_from([16, 32]), bd=st.sampled_from([32, 64]))
    @settings(max_examples=4, deadline=None)
    def test_block_shape_sweep(self, bs, bd):
        from repro.kernels.selective_scan.kernel import selective_scan
        from repro.kernels.selective_scan.ref import selective_scan_ref
        b, s, d_in, n = 1, 64, 64, 8
        x = _rand(10, (b, s, d_in), jnp.float32)
        dt = jax.nn.softplus(_rand(11, (b, s), jnp.float32))
        A = -jnp.exp(_rand(12, (d_in, n), jnp.float32))
        B = _rand(13, (b, s, n), jnp.float32)
        C = _rand(14, (b, s, n), jnp.float32)
        y, h = selective_scan(x, dt, A, B, C, block_s=bs, block_d=bd,
                              interpret=True)
        yr, hr = selective_scan_ref(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-5)

    def test_h0_seeded_resume_matches_full_scan(self):
        """Scanning [0:k) then resuming [k:s) from the carried state must
        reproduce the uninterrupted scan — the contract SSM decode relies on
        when it seeds the kernel with the request's recurrent state."""
        from repro.kernels.selective_scan.kernel import selective_scan
        from repro.kernels.selective_scan.ref import selective_scan_ref
        b, s, k, d_in, n = 1, 64, 32, 64, 8
        x = _rand(20, (b, s, d_in), jnp.float32)
        dt = jax.nn.softplus(_rand(21, (b, s), jnp.float32))
        A = -jnp.exp(_rand(22, (d_in, n), jnp.float32))
        B = _rand(23, (b, s, n), jnp.float32)
        C = _rand(24, (b, s, n), jnp.float32)
        y_full, h_full = selective_scan(x, dt, A, B, C, block_s=16,
                                        block_d=32, interpret=True)
        _, h_mid = selective_scan(x[:, :k], dt[:, :k], A, B[:, :k], C[:, :k],
                                  block_s=16, block_d=32, interpret=True)
        y_res, h_res = selective_scan(x[:, k:], dt[:, k:], A, B[:, k:],
                                      C[:, k:], h_mid, block_s=16, block_d=32,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(y_res), np.asarray(y_full[:, k:]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_res), np.asarray(h_full),
                                   rtol=1e-5, atol=1e-5)
        # the ref path honours h0 identically
        yr, hr = selective_scan_ref(x[:, k:], dt[:, k:], A, B[:, k:],
                                    C[:, k:], h_mid)
        np.testing.assert_allclose(np.asarray(y_res), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("s", [33, 57, 127])
    def test_chunked_pad_path_matches_unpadded_oracle(self, s):
        """Odd sequence lengths exercise _selective_scan_chunked's pad path:
        y and the final carry must match a chunk size that divides s
        exactly (padding must not leak into the carry)."""
        from repro.models.ssm import _selective_scan_chunked
        b, d_in, n = 2, 32, 4
        x = _rand(30, (b, s, d_in), jnp.float32)
        dt = jax.nn.softplus(_rand(31, (b, s, d_in), jnp.float32))
        A = -jnp.exp(_rand(32, (d_in, n), jnp.float32))
        B = _rand(33, (b, s, n), jnp.float32)
        C = _rand(34, (b, s, n), jnp.float32)
        y_pad, h_pad = _selective_scan_chunked(x, dt, A, B, C, chunk=32)
        y_ex, h_ex = _selective_scan_chunked(x, dt, A, B, C, chunk=s)
        np.testing.assert_allclose(np.asarray(y_pad), np.asarray(y_ex),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_pad), np.asarray(h_ex),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False),
                                            ("gpu", None)])
def test_default_interpret_only_on_cpu(monkeypatch, backend, expect):
    """Kernels compile on the TPU, interpret on the CPU, and refuse any
    other backend instead of silently interpreting there."""
    from repro.kernels import _default_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expect is None:
        with pytest.raises(RuntimeError, match="gpu"):
            _default_interpret()
    else:
        assert _default_interpret() is expect
