"""Kernel-parity tests: Pallas flash attention vs the jnp reference
(the methodology of reference tests/unit/test_cuda_forward.py /
test_cuda_backward.py — same inputs, compare within tolerance). Runs the
kernels through the Pallas interpreter on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.attention import (attention,
                                                     xla_attention)
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention


def _make_qkv(rng, b, s, h, d, dtype=jnp.float32):
    shape = (b, s, h, d)
    q = jnp.asarray(rng.standard_normal(shape), dtype)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    return q, k, v


def _seed_of(key):
    """The int32 seed ``flash_attention`` derives from ``dropout_rng``."""
    kd = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    return (kd[0] ^ (kd[-1] << 1)).astype(jnp.int32)


def _dense_oracle(q, k, v, seed, rate, causal, kv_mask=None):
    """Dense float32 attention applying the SAME hash-derived keep mask the
    kernels use, post-softmax (``rate`` 0: no dropout)."""
    from deepspeed_tpu.ops.transformer.flash_attention import \
        dropout_keep_mask

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    b, s, h, d = q.shape
    sk = k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    neg = jnp.finfo(jnp.float32).min
    if causal:
        cm = jnp.tril(jnp.ones((s, sk), jnp.bool_), k=sk - s)
        logits = jnp.where(cm[None, None], logits, neg)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :].astype(bool),
                           logits, neg)
    p = jax.nn.softmax(logits, axis=-1)
    if rate > 0.0:
        rows = jax.lax.broadcasted_iota(jnp.int32, (s, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s, sk), 1)
        bh = (jnp.arange(b)[:, None] * h + jnp.arange(h)[None, :])
        keep = jax.vmap(jax.vmap(
            lambda i: dropout_keep_mask(seed, i, rows, cols, rate)))(bh)
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _assert_matches_oracle(case, q, k, v, ct, *, causal, km, rate, block_q,
                           block_k):
    """The kernels' output and three gradients against the dense oracle
    with the same keep-mask, each in the inputs' dtype."""
    key = jax.random.PRNGKey(11)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, kv_mask=km, block_q=block_q,
            block_k=block_k, dropout_rate=rate,
            dropout_rng=key if rate else None, interpret=True)

    def oracle(q, k, v):
        return _dense_oracle(q, k, v, _seed_of(key), rate, causal, km)

    def with_grads(f):
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(ct.astype(out.dtype))

    tol = 5e-4 if q.dtype == jnp.float32 else 6e-2
    for name, got, want in zip(("out", "dq", "dk", "dv"),
                               with_grads(flash), with_grads(oracle)):
        assert got.dtype == q.dtype, name
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol, err_msg=f"{case}: {name}")


GRID = [
    # (batch, seq, heads, head_dim, causal)
    (2, 128, 2, 64, False),
    (2, 128, 2, 64, True),
    (1, 256, 4, 64, True),
    (2, 128, 2, 128, True),
]


class TestFlashForward:
    @pytest.mark.parametrize("b,s,h,d,causal", GRID)
    def test_matches_reference(self, b, s, h, d, causal):
        rng = np.random.default_rng(0)
        q, k, v = _make_qkv(rng, b, s, h, d)
        ref = xla_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        rng = np.random.default_rng(0)
        q, k, v = _make_qkv(rng, 2, 128, 2, 64, jnp.bfloat16)
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-2, rtol=2e-2)


class TestCrossLength:
    """sq != sk: causal must be bottom-right aligned like the xla reference
    (a decode query block attending a longer KV cache)."""

    @pytest.mark.parametrize("sq,sk", [(128, 256), (128, 384)])
    def test_causal_kv_cache_alignment(self, sq, sk):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((2, sq, 2, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, sk, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, sk, 2, 64)), jnp.float32)
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_causal_kv_cache_grads(self):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(xla_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{n}")


BLOCKS = (128, 256, 512, 1024)


class TestCausalWalk:
    """The block walk of the three kernels under ``causal=True``: only the
    blocks at or under the (bottom-right aligned) diagonal are visited, and
    only those the diagonal crosses are masked."""

    @pytest.mark.parametrize("block_q", BLOCKS)
    @pytest.mark.parametrize("block_k", BLOCKS)
    def test_matches_brute_force(self, block_q, block_k):
        from deepspeed_tpu.ops.transformer.flash_attention import (
            causal_walk, fit_block)

        for seq_q in range(128, 1153, 128):
            for seq_k in range(128, 1153, 128):
                bq, bk = fit_block(block_q, seq_q), fit_block(block_k, seq_k)
                nq, nk = seq_q // bq, seq_k // bk
                allowed = (np.arange(seq_k)[None, :]
                           <= np.arange(seq_q)[:, None] + (seq_k - seq_q))
                blocks = allowed.reshape(nq, bq, nk, bk)
                visited = blocks.any(axis=(1, 3))            # [nq, nk]
                crossed = visited & ~blocks.all(axis=(1, 3))
                walk = causal_walk(seq_q, seq_k, bq, bk)
                what = f"{seq_q}x{seq_k} in {bq}x{bk}"
                assert (walk.visited, walk.crossed, walk.total) == (
                    visited.sum(), crossed.sum(), nq * nk), what

                def marks(runs, n):
                    seen, masked = np.zeros(n, bool), np.zeros(n, bool)
                    for first, count, is_masked in runs:
                        blocks = slice(first, first + count)
                        assert not seen[blocks].any(), what     # disjoint
                        seen[blocks] = True
                        masked[blocks] = is_masked
                    return seen, masked

                for qi in range(nq):          # forward and dq: a q-block's row
                    seen, masked = marks(walk.kv_runs(qi), nk)
                    assert (seen == visited[qi]).all(), (what, qi)
                    assert (masked == crossed[qi]).all(), (what, qi)
                for ki in range(nk):          # dkv: a kv-block's column
                    seen, masked = marks(walk.q_runs(ki), nq)
                    assert (seen == visited[:, ki]).all(), (what, ki)
                    assert (masked == crossed[:, ki]).all(), (what, ki)
                # what the kernels compute from a traced program_id (a
                # truncating lax.div) is what Python's ints give
                for runs, ids in ((walk.kv_runs, nq), (walk.q_runs, nk)):
                    traced = runs(jnp.arange(ids, dtype=jnp.int32))
                    for j, (first, count, _) in enumerate(traced):
                        want = [runs(i)[j] for i in range(ids)]
                        np.testing.assert_array_equal(
                            np.broadcast_to(first, (ids,)),
                            [w[0] for w in want])
                        np.testing.assert_array_equal(
                            np.broadcast_to(count, (ids,)),
                            [w[1] for w in want])
                # one diagonal block a program: written out, not looped over
                if bq == bk and seq_q == seq_k:
                    for count in (walk.kv_runs(jnp.int32(0))[1][1],
                                  walk.q_runs(jnp.int32(0))[0][1]):
                        assert isinstance(count, int) and count == 1, what

    @pytest.mark.parametrize("seq,head_dim,share", [
        (1024, 64, 0.75),       # gpt2m-train-s1024 (the square: 1.0)
        (4096, 256, 0.5625),    # glm47flash-train-s4096 (512x1024: 0.625)
    ])
    def test_default_blocks_visit_little_more_than_the_triangle(
            self, seq, head_dim, share):
        from deepspeed_tpu.ops.transformer.flash_attention import (
            causal_walk, fitted_blocks)

        walk = causal_walk(seq, seq, *fitted_blocks(True, seq, seq, head_dim))
        assert walk.visited / walk.total <= share, walk
        assert walk.crossed < walk.visited        # interior blocks exist

    def test_non_causal_defaults_are_what_they_were(self):
        from deepspeed_tpu.ops.transformer.flash_attention import \
            fitted_blocks

        assert fitted_blocks(False, 4096, 4096, 64) == (512, 1024)
        assert fitted_blocks(False, 512, 512, 64) == (512, 512)
        assert fitted_blocks(True, 1024, 1024, 64, 128, 256) == (128, 256)

    @pytest.mark.parametrize("impl,causal", [
        ("pallas", True),
        ("pallas", False),      # the non-causal walk is the whole rectangle
        ("xla", True),
    ])
    def test_dispatch_logs_the_walk_once_per_shape(self, monkeypatch, impl,
                                                   causal):
        from deepspeed_tpu.ops.transformer import attention as att
        from deepspeed_tpu.ops.transformer.flash_attention import (
            causal_walk, fitted_blocks)

        lines = []
        monkeypatch.setattr(att.logger, "info", lines.append)
        att._log_auto_choice.__wrapped__(impl, causal, 1024, 1024, 64)
        (line,) = lines
        assert f"-> {impl} (seq_q=1024, seq_k=1024, head_dim=64" in line
        if impl == "pallas" and causal:
            w = causal_walk(1024, 1024, *fitted_blocks(True, 1024, 1024, 64))
            assert (f"blocks visited {w.visited} of {w.total}, "
                    f"{w.crossed} masked; backward: one kernel") in line
        else:
            assert "visited" not in line


class TestCausalWalkParity:
    """Forward and the three gradients against the dense oracle where the
    diagonal crosses several blocks and interior blocks exist."""

    CASES = {
        # name: (seq_q, seq_k, block_q, block_k, dtype, kv_mask, dropout)
        "s1024-defaults-f32": (1024, 1024, None, None, jnp.float32, False, 0.0),
        "s1024-defaults-bf16": (1024, 1024, None, None, jnp.bfloat16, False,
                                0.0),
        "q256-k512-f32": (256, 512, 128, 128, jnp.float32, False, 0.0),
        "q256-k512-bf16": (256, 512, 128, 128, jnp.bfloat16, False, 0.0),
        "s512-kvmask-f32": (512, 512, 128, 128, jnp.float32, True, 0.0),
        "s512-dropout-f32": (512, 512, 128, 128, jnp.float32, False, 0.3),
        "s512-wide-k-f32": (512, 512, 128, 256, jnp.float32, False, 0.0),
        "s512-wide-q-kvmask-dropout-bf16": (512, 512, 256, 128, jnp.bfloat16,
                                            True, 0.3),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_forward_and_gradients(self, case):
        sq, sk, bq, bk, dtype, masked, rate = self.CASES[case]
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((1, sq, 2, 64)), dtype)
        k = jnp.asarray(rng.standard_normal((1, sk, 2, 64)), dtype)
        v = jnp.asarray(rng.standard_normal((1, sk, 2, 64)), dtype)
        ct = jnp.asarray(rng.standard_normal((1, sq, 2, 64)), jnp.float32)
        km = None
        if masked:      # pad keys at the end; key 0 stays for every row
            km = jnp.asarray(np.arange(sk)[None, :] < sk - 100, jnp.int32)
        _assert_matches_oracle(case, q, k, v, ct, causal=True, km=km,
                               rate=rate, block_q=bq, block_k=bk)


def _pallas_call_names(jaxpr):
    """``name=`` of every ``pallas_call`` in a jaxpr, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_pallas_call_names(sub))
    return names


class TestOneBackwardKernel:
    """dQ, dK and dV come from ONE kernel: dQ is summed over the kv-blocks in
    a scratch that every head zeroes anew and writes out at its last
    kv-block. Held to the dense oracle where a q-block's sum runs over
    several kv-blocks, over several heads, and at one-block lengths."""

    CASES = {
        # name: (batch, heads, seq_q, seq_k, block_q, block_k, dtype, causal,
        #        kv_mask, dropout)
        "causal-4-kv-blocks-f32": (2, 2, 512, 512, 128, 128, jnp.float32,
                                   True, False, 0.0),
        "causal-4-kv-blocks-bf16": (2, 2, 512, 512, 128, 128, jnp.bfloat16,
                                    True, False, 0.0),
        "whole-square-4-kv-blocks-f32": (2, 2, 512, 512, 128, 128,
                                         jnp.float32, False, False, 0.0),
        "whole-square-wide-q-f32": (1, 3, 512, 512, 256, 128, jnp.float32,
                                    False, False, 0.0),
        "causal-q256-k512-f32": (2, 2, 256, 512, 128, 128, jnp.float32, True,
                                 False, 0.0),
        "whole-q512-k256-f32": (2, 2, 512, 256, 128, 128, jnp.float32, False,
                                False, 0.0),
        "whole-q128-k512-kvmask-bf16": (2, 2, 128, 512, 128, 128,
                                        jnp.bfloat16, False, True, 0.0),
        "whole-kvmask-dropout-f32": (2, 2, 512, 512, 128, 128, jnp.float32,
                                     False, True, 0.3),
        "causal-kvmask-dropout-f32": (2, 2, 512, 512, 128, 128, jnp.float32,
                                      True, True, 0.3),
        "causal-s640-one-block-f32": (1, 2, 640, 640, None, None,
                                      jnp.float32, True, False, 0.0),
        "causal-s896-one-block-bf16": (1, 2, 896, 896, None, None,
                                       jnp.bfloat16, True, False, 0.0),
        "whole-s640-f32": (1, 2, 640, 640, None, None, jnp.float32, False,
                           False, 0.0),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_output_and_three_gradients(self, case):
        b, h, sq, sk, bq, bk, dtype, causal, masked, rate = self.CASES[case]
        rng = np.random.default_rng(13)
        q = jnp.asarray(rng.standard_normal((b, sq, h, 64)), dtype)
        k = jnp.asarray(rng.standard_normal((b, sk, h, 64)), dtype)
        v = jnp.asarray(rng.standard_normal((b, sk, h, 64)), dtype)
        ct = jnp.asarray(rng.standard_normal((b, sq, h, 64)), dtype)
        km = None
        if masked:      # each batch row pads another number of trailing keys
            pad = 60 + 70 * np.arange(b)[:, None]
            km = jnp.asarray(np.arange(sk)[None, :] < sk - pad, jnp.int32)
        _assert_matches_oracle(case, q, k, v, ct, causal=causal, km=km,
                               rate=rate, block_q=bq, block_k=bk)

    @pytest.mark.parametrize("masked", [False, True],
                             ids=["plain", "kv_mask"])
    def test_the_backward_is_one_pallas_call(self, masked):
        q = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
        km = jnp.ones((1, 256), jnp.int32) if masked else None

        def grads(q, k, v, ct):
            return jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, kv_mask=km, block_q=128, block_k=128,
                interpret=True), q, k, v)[1](ct)

        names = _pallas_call_names(jax.make_jaxpr(grads)(q, q, q, q).jaxpr)
        assert sorted(names) == ["flash_bwd", "flash_fwd"]


class TestKvMask:
    """Key-padding mask parity (the BERT attention_mask path): masked keys
    must contribute to neither the normaliser nor the output, matching the
    xla reference's where-on-logits semantics."""

    def _mask(self, rng, b, s):
        lengths = rng.integers(1, s + 1, (b,))
        return jnp.asarray(np.arange(s)[None, :] < lengths[:, None],
                           jnp.int32)

    @pytest.mark.parametrize("b,s,h,d,causal", GRID)
    def test_forward(self, b, s, h, d, causal):
        rng = np.random.default_rng(4)
        q, k, v = _make_qkv(rng, b, s, h, d)
        km = self._mask(rng, b, s)
        ref = xla_attention(q, k, v, causal=causal,
                            mask=km[:, None, None, :])
        out = flash_attention(q, k, v, causal=causal, kv_mask=km,
                              interpret=True)
        # Padded QUERY rows may differ (flash never sees query masks; the
        # model multiplies them out downstream) — compare valid rows only.
        valid = np.asarray(km, bool)
        np.testing.assert_allclose(np.asarray(out)[valid],
                                   np.asarray(ref)[valid],
                                   atol=2e-5, rtol=2e-5)

    def test_all_ones_mask_matches_unmasked(self):
        rng = np.random.default_rng(5)
        q, k, v = _make_qkv(rng, 2, 128, 2, 64)
        km = jnp.ones((2, 128), jnp.int32)
        out_m = flash_attention(q, k, v, kv_mask=km, interpret=True)
        out = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out_m), np.asarray(out),
                                   atol=1e-6, rtol=1e-6)

    def test_grads(self):
        rng = np.random.default_rng(6)
        b, s, h, d = 2, 128, 2, 64
        q, k, v = _make_qkv(rng, b, s, h, d)
        km = self._mask(rng, b, s)
        valid = np.asarray(km, bool)
        # Zero the cotangent on padded query rows so both sides see the
        # same upstream gradient on rows the model would keep.
        w = jnp.asarray(valid, jnp.float32)[:, :, None, None]

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, kv_mask=km, interpret=True)
            return jnp.sum((o * w) ** 2)

        def loss_ref(q, k, v):
            o = xla_attention(q, k, v, mask=km[:, None, None, :])
            return jnp.sum((o * w) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name} mismatch")


class TestDispatchMask:
    def test_pallas_accepts_padding_mask_forms(self):
        from deepspeed_tpu.ops.transformer.attention import _as_kv_mask
        m2 = jnp.ones((2, 128))
        assert _as_kv_mask(m2, 2, 128) is m2
        m4 = jnp.ones((2, 1, 1, 128))
        assert _as_kv_mask(m4, 2, 128).shape == (2, 128)
        full = jnp.ones((2, 4, 128, 128))
        assert _as_kv_mask(full, 2, 128) is None


class TestFlashBackward:
    @pytest.mark.parametrize("b,s,h,d,causal", GRID)
    def test_grads_match_reference(self, b, s, h, d, causal):
        rng = np.random.default_rng(1)
        q, k, v = _make_qkv(rng, b, s, h, d)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name} mismatch")


def test_auto_dispatch_shapes_always_run():
    """Regression: every shape _pallas_ok admits must execute — the tuned
    512/1024 block defaults must self-fit to 128-multiple sequences that
    are not multiples of the block (e.g. 768)."""
    import numpy as np

    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    for seq in (128, 256, 640, 768, 1152):
        q = jnp.asarray(rng.standard_normal((1, seq, 2, 64)), jnp.float32)
        out = flash_attention(q, q, q, causal=True, interpret=True)
        assert out.shape == q.shape
        assert np.isfinite(np.asarray(out)).all()


class TestInKernelDropout:
    """In-kernel attention dropout (reference dropout_kernels.cu,
    ds_transformer_cuda.cpp:168-190). The keep-mask comes from a
    counter-based hash shared between the kernels and this oracle, so
    parity is exact — fwd AND bwd regenerate the identical mask."""

    RATE = 0.3

    def _qkv(self, rng, b=2, s=256, h=2, d=64):
        mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)),
                                 jnp.float32)
        return mk(), mk(), mk()

    def _flash(self, q, k, v, seed_key, causal, kv_mask=None):
        return flash_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                               dropout_rate=self.RATE, dropout_rng=seed_key,
                               interpret=True)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_oracle(self, causal):
        rng = np.random.default_rng(0)
        q, k, v = self._qkv(rng)
        key = jax.random.PRNGKey(5)
        out = self._flash(q, k, v, key, causal)
        ref = _dense_oracle(q, k, v, _seed_of(key), self.RATE, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_forward_with_kv_mask(self):
        rng = np.random.default_rng(1)
        q, k, v = self._qkv(rng)
        mask = np.ones((2, 256), np.int32)
        mask[:, 200:] = 0
        mask = jnp.asarray(mask)
        key = jax.random.PRNGKey(6)
        out = self._flash(q, k, v, key, False, kv_mask=mask)
        ref = _dense_oracle(q, k, v, _seed_of(key), self.RATE, False,
                           kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_oracle(self, causal):
        rng = np.random.default_rng(2)
        q, k, v = self._qkv(rng)
        key = jax.random.PRNGKey(7)
        seed = _seed_of(key)

        def loss_flash(q, k, v):
            o = self._flash(q, k, v, key, causal)
            w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)
            return jnp.sum(o * w) / o.size

        def loss_ref(q, k, v):
            o = _dense_oracle(q, k, v, seed, self.RATE, causal)
            w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)
            return jnp.sum(o * w) / o.size

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name}")

    def test_seed_determinism_and_variation(self):
        rng = np.random.default_rng(3)
        q, k, v = self._qkv(rng)
        k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
        a = self._flash(q, k, v, k1, False)
        b = self._flash(q, k, v, k1, False)
        c = self._flash(q, k, v, k2, False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.allclose(np.asarray(a), np.asarray(c))

    def test_keep_fraction(self):
        from deepspeed_tpu.ops.transformer.flash_attention import \
            dropout_keep_mask

        rows = jax.lax.broadcasted_iota(jnp.int32, (512, 512), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (512, 512), 1)
        keep = dropout_keep_mask(jnp.int32(123), 3, rows, cols, 0.3)
        frac = float(jnp.mean(keep.astype(jnp.float32)))
        assert abs(frac - 0.7) < 0.01, frac

    def test_dispatch_routes_dropout_to_pallas(self):
        """attention(impl='pallas') with dropout must run the kernel (the
        round-2 gap: it raised and auto fell back to xla everywhere)."""
        from deepspeed_tpu.ops.transformer.attention import attention

        rng = np.random.default_rng(4)
        q, k, v = self._qkv(rng, s=512)
        out = attention(q, k, v, causal=True, dropout_rate=0.1,
                        dropout_rng=jax.random.PRNGKey(0),
                        deterministic=False, impl="pallas")
        assert np.isfinite(np.asarray(out)).all()


class TestDispatchBlockQuality:
    def test_gate_admits_all_128_multiples(self):
        """Round-4 re-measurement (tools/probe_pad_dispatch.py): the flash
        kernel wins at EVERY 128-multiple length >= 512 including the
        degraded-block ones (640/896), dropout on and off — the r3 XLA
        fallback is gone. Short sequences still stay on XLA."""
        from deepspeed_tpu.ops.transformer import attention as att

        for s in (512, 640, 768, 896, 1024, 1152, 1536, 2048):
            q = jnp.zeros((2, s, 4, 64), jnp.bfloat16)
            assert att._pallas_ok(q, q, None, None), s
            assert att._pallas_ok(q, q, None, None, dropout_active=True), s
        q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
        assert not att._pallas_ok(q, q, None, None)   # below the crossover
        q = jnp.zeros((2, 576, 4, 64), jnp.bfloat16)
        assert not att._pallas_ok(q, q, None, None)   # not a 128 multiple


class TestPaddedDispatch:
    """impl='pallas_pad' (round-3 VERDICT task 8): odd 128-multiple
    self-attention lengths run the flash kernel on 512-padded sequences
    with the tail masked — numerics must match xla exactly (pad queries
    sliced, pad keys masked)."""

    @pytest.mark.parametrize("seq", [640, 896])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla(self, seq, causal):
        rng = np.random.default_rng(0)
        shape = (2, seq, 4, 64)
        q = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        k = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        ref = attention(q, k, v, causal=causal, impl="xla")
        pad = attention(q, k, v, causal=causal, impl="pallas_pad")
        np.testing.assert_allclose(np.asarray(pad), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_xla_with_key_mask(self):
        rng = np.random.default_rng(1)
        shape = (2, 640, 4, 64)
        q = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        k = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        mask = np.ones((2, 640), np.int32)
        mask[:, 600:] = 0
        ref = attention(q, k, v, mask=jnp.asarray(mask), impl="xla")
        pad = attention(q, k, v, mask=jnp.asarray(mask), impl="pallas_pad")
        np.testing.assert_allclose(np.asarray(pad), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_xla(self):
        rng = np.random.default_rng(2)
        shape = (1, 640, 2, 64)
        q = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        k = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1

        def loss(impl):
            return lambda q, k, v: jnp.sum(
                attention(q, k, v, causal=True, impl=impl) ** 2)

        g_ref = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        g_pad = jax.grad(loss("pallas_pad"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_pad, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, rtol=3e-5)

    def test_dropout_runs_and_is_seeded(self):
        rng = np.random.default_rng(3)
        shape = (1, 640, 2, 64)
        q = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
        key = jax.random.PRNGKey(7)
        out1 = attention(q, q, q, causal=True, impl="pallas_pad",
                         dropout_rate=0.1, dropout_rng=key,
                         deterministic=False)
        out2 = attention(q, q, q, causal=True, impl="pallas_pad",
                         dropout_rate=0.1, dropout_rng=key,
                         deterministic=False)
        assert np.all(np.isfinite(np.asarray(out1, np.float32)))
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


class TestPerShardOnAMesh:
    """GSPMD cannot partition a Mosaic call, so on a multi-device mesh the
    flash kernel runs once per shard (attention._flash_per_shard): batch
    over the data-like axes, heads over the model axis. Placement, not
    math — the result must equal the unsharded reference. (That the region
    satisfies the TPU compiler is tests/test_tpu_lowering.py's half.)"""

    def _case(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        rng = np.random.default_rng(4)
        sh = NamedSharding(mesh, P(("dcn", "data"), None, "model"))
        q, k, v = (jax.device_put(
            jnp.asarray(rng.standard_normal((8, 128, 4, 64)), jnp.float32)
            * 0.3, sh) for _ in range(3))
        keep = rng.random((8, 128)) > 0.2
        keep[:, 0] = True      # no fully-masked causal row
        mask = jnp.asarray(keep)

        def loss(impl, **kw):
            return lambda q, k, v: jnp.sum(attention(
                q, k, v, causal=True, mask=mask, impl=impl, **kw) ** 2)

        return (q, k, v), loss

    @pytest.mark.parametrize("how", ["mesh argument", "pinned by the engine"])
    def test_matches_unsharded_reference(self, eight_devices, how):
        from deepspeed_tpu.parallel.mesh import build_mesh, pinned_mesh
        mesh = build_mesh(data=2, model=2, slices=2)
        args, loss = self._case(mesh)
        want = jax.value_and_grad(loss("xla"), argnums=(0, 1, 2))(*args)
        if how == "mesh argument":
            got = jax.jit(jax.value_and_grad(
                loss("pallas", mesh=mesh), argnums=(0, 1, 2)))(*args)
        else:
            def pinned(*a):
                with pinned_mesh(mesh):
                    return jax.value_and_grad(loss("pallas"),
                                              argnums=(0, 1, 2))(*a)
            got = jax.jit(pinned)(*args)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, rtol=3e-5)
        # really per shard: the outputs kept the batch/head sharding
        assert got[1][0].sharding.shard_shape((8, 128, 4, 64)) == \
            (2, 128, 2, 64)

    def test_dropout_masks_differ_across_shards(self, eight_devices):
        from deepspeed_tpu.parallel.mesh import build_mesh
        mesh = build_mesh(data=8)
        q = jnp.ones((8, 128, 2, 64), jnp.float32)
        out = jax.jit(lambda q: attention(
            q, q, q, impl="pallas", mesh=mesh, dropout_rate=0.5,
            dropout_rng=jax.random.PRNGKey(0), deterministic=False))(q)
        rows = np.asarray(out).reshape(8, -1)
        # identical inputs per sample: only the dropout mask can differ
        assert len({r.tobytes() for r in rows}) > 1
