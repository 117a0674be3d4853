"""Pallas kernels in interpreter mode vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas_kernels import flash_attention, fused_scale
from horovod_tpu.parallel.ring_attention import reference_attention


class TestFusedScale:
    def test_scale_matches(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (300,), jnp.float32)
        out = fused_scale(x, 2.5, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.5,
                                   rtol=1e-6)

    def test_scale_with_cast(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), jnp.float32)
        out = fused_scale(x, 0.5, out_dtype=jnp.bfloat16, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(x) * 0.5,
            rtol=1e-2, atol=1e-2)

    def test_zero_factor(self):
        x = jnp.ones((17,), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(fused_scale(x, 0.0, interpret=True)), 0.0)


class TestFlashAttention:
    @pytest.mark.parametrize("causal,bq,bk", [
        (False, 16, 16), (True, 16, 16),
        (True, 16, 32),  # partial diagonal block (block_q < block_k)
    ])
    def test_matches_dense(self, causal, bq, bk):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        shape = (2, 64, 2, 16)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
        out = flash_attention(q, k, v, causal=causal, block_q=bq,
                              block_k=bk, interpret=True)
        expected = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_matches_dense(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        shape = (1, 32, 2, 8)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=8, block_k=8,
                                           interpret=True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal,bq,bk", [
        (False, 8, 8), (False, 16, 8), (True, 16, 8), (True, 8, 8),
        (True, 8, 16),   # block_q < block_k: diagonal block is partial
    ])
    def test_bwd_kernel_matches_dense(self, causal, bq, bk):
        """The Pallas FlashAttention-2 backward (dQ + dK/dV kernels, fed
        by the forward's saved logsumexp) must match the dense VJP on
        every input, incl. uneven block_q/block_k ratios."""
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        shape = (2, 32, 2, 8)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32)
                   for kk in ks[:3])
        g = jax.random.normal(ks[3], shape, jnp.float32)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk, interpret=True)

        def dense(q, k, v):
            return reference_attention(q, k, v, causal=causal)

        _, vjp_f = jax.vjp(flash, q, k, v)
        _, vjp_d = jax.vjp(dense, q, k, v)
        for a, b, name in zip(vjp_f(g), vjp_d(g), "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} mismatch (causal={causal})")

    def test_fallback_on_ragged_seq(self):
        """Non-divisible seq falls back to the dense path (still correct)."""
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        shape = (1, 30, 2, 8)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        expected = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)


class TestFlashAttentionBf16:
    """bf16-native kernel path: the astype(native-dtype) casts before the
    MXU dots must be exercised by bf16 inputs (fp32 inputs make them
    identity no-ops), with accumulators staying fp32."""

    def test_forward_matches_dense_bf16(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        shape = (2, 64, 2, 16)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks)
        out = flash_attention(q, k, v, causal=True, block_q=16,
                              block_k=16, interpret=True)
        assert out.dtype == jnp.bfloat16
        expected = reference_attention(q.astype(jnp.float32),
                                       k.astype(jnp.float32),
                                       v.astype(jnp.float32), causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expected), rtol=0.05,
                                   atol=0.05)

    def test_gradients_match_dense_bf16(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        shape = (1, 32, 2, 16)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16,
                interpret=True).astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            assert a.dtype == jnp.bfloat16, name
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=0.1, atol=0.1, err_msg=f"d{name}")


def _dense_vjp(q, k, v, g, causal):
    f32 = jnp.float32
    _, vjp = jax.vjp(lambda q, k, v: reference_attention(
        q, k, v, causal=causal), q.astype(f32), k.astype(f32), v.astype(f32))
    return vjp(g.astype(f32))


def _qkvg(seed, shape, dtype=jnp.float32):
    return tuple(jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                 for kk in jax.random.split(jax.random.PRNGKey(seed), 4))


class TestFlashBackwardOneKernel:
    """The backward is one Pallas call (``flash_bwd``): a K block at a
    time against its row's Q / dO blocks, the scores and ``p`` rebuilt
    once a block pair, dQ summed in an fp32 VMEM scratch over the row's
    K blocks."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("t,bq,bk", [
        (512, 128, 256), (512, 256, 128),       # block_q != block_k
        (384, 512, 512), (640, 512, 512),       # no multiple of 512
    ])
    # q / k and v widths: whole 128-lane tiles (the gradients added up
    # as they are given) and not (transposed: flash_bwd_transposed)
    @pytest.mark.parametrize("d,dv", [(16, 16), (128, 128), (192, 128),
                                      (64, 64)])
    def test_dq_dk_dv_match_the_dense_gradient(self, causal, t, bq, bk,
                                               d, dv):
        q, k = _qkvg(11, (1, t, 2, d))[:2]
        v, g = _qkvg(11, (1, t, 2, dv))[2:]
        _, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk,
            interpret=True), q, k, v)
        for a, b, name in zip(vjp(g), _dense_vjp(q, k, v, g, causal), "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} (causal={causal}, seq {t}, {bq}/{bk}, "
                        f"widths {d}/{dv})")

    def test_the_backward_is_one_call_named_flash_bwd(self):
        q, k, v, g = _qkvg(12, (1, 256, 2, 16))
        jaxpr = str(jax.make_jaxpr(lambda q, k, v, g: jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128,
                interpret=True), q, k, v)[1](g))(q, k, v, g))
        assert jaxpr.count("pallas_call[") == 2
        assert jaxpr.count("name=flash_fwd") == 1
        assert jaxpr.count("name=flash_bwd") == 1

    def test_bf16_operands(self):
        """Band as ``TestFlashAttentionBf16``: operands reach the
        products in bf16, the sums stay fp32."""
        q, k, v, g = _qkvg(13, (1, 256, 2, 16), jnp.bfloat16)
        _, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=64,
            interpret=True), q, k, v)
        for a, b, name in zip(vjp(g), _dense_vjp(q, k, v, g, True), "qkv"):
            assert a.dtype == jnp.bfloat16, name
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b), rtol=0.1,
                atol=0.1, err_msg=f"d{name}")

    def test_gqa_repeated_heads(self):
        """``Attention`` repeats the key/value heads before the kernel:
        the gradient of a key/value head is the sum over its query
        heads' copies, through the one call."""
        q, _, _, g = _qkvg(14, (1, 256, 4, 16))
        _, k, v, _ = _qkvg(15, (1, 256, 2, 16))

        def over(attend):
            def f(q, k, v):
                return attend(q, jnp.repeat(k, 2, axis=2),
                              jnp.repeat(v, 2, axis=2))
            return jax.vjp(f, q, k, v)[1](g)

        got = over(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True))
        want = over(lambda q, k, v: reference_attention(
            q, k, v, causal=True))
        for a, b, name in zip(got, want, "qkv"):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("bq,bk,d,dv", [
        (16, 16, 8, 8), (32, 16, 8, 8),
        (16, 16, 192, 128),         # latent attention's two widths
    ])
    def test_zigzag_positions_with_a_global_lse_and_delta(self, bq, bk,
                                                          d, dv):
        """The ring's use: this rank's Q rows sit at zigzag global
        positions, each visiting K/V block brings its own, and ``lse``
        / ``delta`` are those of the softmax over the whole ring's
        keys.  dQ is the sum over the visiting blocks, dK / dV of a
        block are its own."""
        from horovod_tpu.ops.pallas_kernels import _flash_bwd

        world, t, h = 2, 32, 2
        full = world * t
        q, k = _qkvg(16, (1, full, h, d))[:2]
        v = _qkvg(16, (1, full, h, dv))[2]
        g = _qkvg(17, (1, t, h, dv))[0]
        # zigzag: rank r holds chunks r and 2 world - 1 - r
        chunks = np.arange(full).reshape(2 * world, -1)
        pos = [np.concatenate([chunks[r], chunks[2 * world - 1 - r]])
               for r in range(world)]
        mine = pos[0]
        scale = d ** -0.5

        def rows_out(q, k, v):      # this rank's rows of the attention
            return reference_attention(q, k, v, causal=True)[:, mine]

        out, vjp = jax.vjp(rows_out, q, k, v)
        dq_want, dk_want, dv_want = vjp(g)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, mine], k) * scale
        s = jnp.where((mine[:, None] >= np.arange(full)[None, :]),
                      s, -jnp.inf)
        lse = jax.scipy.special.logsumexp(s, axis=-1).reshape(h, t)
        delta = (g * out).sum(-1).transpose(0, 2, 1).reshape(h, t)
        lse8 = jnp.broadcast_to(lse[:, None, :], (h, 8, t))
        dq = 0
        for theirs in pos:
            dq_b, dk_b, dv_b = _flash_bwd(
                q[:, mine], k[:, theirs], v[:, theirs], out, lse8, g, True,
                scale, bq, bk, True, qpos=jnp.asarray(mine),
                kpos=jnp.asarray(theirs), delta=delta)
            dq = dq + dq_b
            np.testing.assert_allclose(dk_b, dk_want[:, theirs],
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(dv_b, dv_want[:, theirs],
                                       rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(dq, dq_want[:, mine],
                                   rtol=2e-4, atol=2e-4)

    def test_dq_adds_up_in_fp32_over_the_k_blocks(self):
        """bf16 operands, a row of 32 K blocks: dQ out of the kernel is
        as near the float64 oracle as a sum kept in fp32 and cast once,
        and nearer than a sum rounded to bf16 after every K block."""
        t, d, blk = 4096, 16, 128
        q, k, v, g = _qkvg(18, (1, t, 1, d), jnp.bfloat16)
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=False, block_q=blk, block_k=blk,
            interpret=True), q, k, v)
        dq = np.asarray(vjp(g)[0], np.float64)[0, :, 0]

        q64, k64, v64, g64 = (np.asarray(x, np.float64)[0, :, 0]
                              for x in (q, k, v, g))
        s = q64 @ k64.T * d ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o64 = p @ v64
        dp = g64 @ v64.T
        ds = p * (dp - (g64 * o64).sum(-1, keepdims=True))
        oracle = ds @ k64 * d ** -0.5

        def summed(acc_dtype):      # the kernel's arithmetic a K block
            acc = jnp.zeros((t, d), acc_dtype)
            for j in range(t // blk):
                cols = slice(j * blk, (j + 1) * blk)
                part = jnp.dot(jnp.asarray(ds[:, cols], jnp.bfloat16),
                               k[0, cols, 0],
                               preferred_element_type=jnp.float32)
                acc = (acc.astype(jnp.float32) + part).astype(acc_dtype)
            return np.asarray((acc.astype(jnp.float32) * d ** -0.5)
                              .astype(jnp.bfloat16), np.float64)

        def err(x):
            return np.abs(x - oracle).mean()

        assert err(dq) <= 1.1 * err(summed(jnp.float32))
        assert err(dq) < 0.6 * err(summed(jnp.bfloat16))


class TestBlockFitting:
    """Seq lens that are multiples of 128 but not of the 512 default must
    shrink the block and stay on the flash kernel, never fall back to
    the dense O(T^2) path."""

    @pytest.mark.parametrize("t", [640, 1280, 384])
    def test_non_512_multiple_seq_uses_flash(self, t):
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        shape = (1, t, 1, 16)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        expected = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)


class TestFusedConvBnReluBwd:
    """One-pass backward of relu(bn_inference(conv3x3)) — the ResNet
    block-segment kernel.  Oracle: jax.grad of the unfused segment."""

    def _setup(self, n=4, h=6, w=6, cin=128, c=128, dtype=jnp.float32):
        import numpy as np

        rng = np.random.RandomState(0)
        a = jnp.asarray(rng.randn(n, h, w, cin), dtype)
        k = jnp.asarray(rng.randn(3, 3, cin, c) * 0.05, jnp.float32)
        gamma = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
        beta = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
        mean = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
        var = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
        cot = jnp.asarray(rng.randn(n, h, w, c), dtype)
        return a, k, gamma, beta, mean, var, cot

    @staticmethod
    def _unfused(a, k, gamma, beta, mean, var):
        dn = jax.lax.conv_dimension_numbers(
            a.shape, k.shape, ("NHWC", "HWIO", "NHWC"))
        y = jax.lax.conv_general_dilated(
            a, k.astype(a.dtype), (1, 1), "SAME", dimension_numbers=dn)
        s = gamma / jnp.sqrt(var + 1e-5)
        z = y.astype(jnp.float32) * s + (beta - mean * s)
        return jnp.maximum(z, 0.0).astype(a.dtype)

    def test_matches_autodiff_of_unfused_segment(self):
        from horovod_tpu.ops.pallas_kernels import fused_conv_bn_relu

        a, k, gamma, beta, mean, var, cot = self._setup()

        def loss_u(a, k, gamma, beta):
            return (self._unfused(a, k, gamma, beta, mean, var)
                    .astype(jnp.float32) * cot).sum()

        def loss_f(a, k, gamma, beta):
            return (fused_conv_bn_relu(a, k, gamma, beta, mean, var,
                                       interpret=True)
                    .astype(jnp.float32) * cot).sum()

        import numpy as np

        np.testing.assert_allclose(
            self._unfused(a, k, gamma, beta, mean, var),
            fused_conv_bn_relu(a, k, gamma, beta, mean, var,
                               interpret=True), rtol=2e-5, atol=2e-5)
        gu = jax.grad(loss_u, argnums=(0, 1, 2, 3))(a, k, gamma, beta)
        gf = jax.grad(loss_f, argnums=(0, 1, 2, 3))(a, k, gamma, beta)
        for name, u, f in zip(("da", "dw", "dgamma", "dbeta"), gu, gf):
            np.testing.assert_allclose(u, f, rtol=2e-4, atol=2e-4,
                                       err_msg=name)

    def test_odd_batch_and_bigger_spatial(self):
        """nb must divide N (grid tiling): N=3 forces nb=1, H=W=10
        exercises multi-row padding slices."""
        import numpy as np

        from horovod_tpu.ops.pallas_kernels import (
            _cbr_bwd_reference,
            fused_conv_bn_relu_bwd,
        )

        a, k, gamma, beta, mean, var, cot = self._setup(n=3, h=10, w=10)
        s = gamma / jnp.sqrt(var + 1e-5)
        b = self._unfused(a, k, gamma, beta, mean, var)
        got = fused_conv_bn_relu_bwd(cot, b, a, k, gamma, beta, s,
                                     interpret=True)
        want = _cbr_bwd_reference(cot, b, a, k, gamma, beta, s)
        for name, g, w_ in zip(("da", "dw", "dgamma", "dbeta"), got, want):
            np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-4,
                                       err_msg=name)

    def test_non_lane_channels_fall_back(self):
        """C not a 128-multiple stays on the jnp fallback (identical
        numerics by construction) — never a Mosaic lowering risk."""
        import numpy as np

        from horovod_tpu.ops.pallas_kernels import (
            _cbr_bwd_reference,
            fused_conv_bn_relu_bwd,
        )

        a, k, gamma, beta, mean, var, cot = self._setup(cin=64, c=64)
        s = gamma / jnp.sqrt(var + 1e-5)
        b = self._unfused(a, k, gamma, beta, mean, var)
        got = fused_conv_bn_relu_bwd(cot, b, a, k, gamma, beta, s)
        want = _cbr_bwd_reference(cot, b, a, k, gamma, beta, s)
        for g, w_ in zip(got, want):
            np.testing.assert_allclose(g, w_, rtol=1e-6)

    def test_resnet_fused_flag_trains(self, hvd_runtime):
        """ResNet50(fused_bwd=True) wires the custom-vjp segments into
        a real train step (CPU falls back to the identical-numerics jnp
        path; the kernel itself is covered in interpret mode above)."""
        import numpy as np
        import optax

        from horovod_tpu.models.resnet import ResNet50

        hvd = hvd_runtime
        model = ResNet50(num_classes=10, fused_bwd=True)

        def loss_fn(params, batch):
            import optax as _optax

            logits = model.apply(params, batch["x"], train=False)
            return _optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()

        step = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.01))
        x0 = jnp.zeros((1, 32, 32, 3), jnp.float32)
        params, opt = step.init(jax.jit(
            lambda kk: model.init(kk, x0, train=False))(
                jax.random.PRNGKey(0)))
        rng = np.random.RandomState(0)
        batch = step.shard_batch({
            "x": jnp.asarray(rng.rand(16, 32, 32, 3), jnp.float32),
            "y": jnp.asarray(rng.randint(0, 10, (16,)), jnp.int32)})
        params, opt, loss = step(params, opt, batch)
        assert np.isfinite(float(loss))


class TestNonTileShapeParity:
    """Interpreter-mode parity of the EXISTING kernels at
    non-tile-multiple shapes (odd trailing dims, seq lengths off the
    block grid) vs their jnp fallbacks — the shapes the happy-path
    tests above never touch (ISSUE 9 satellite)."""

    @pytest.mark.parametrize("shape", [(1000,), (3, 77), (5, 130),
                                       (7, 13, 11), (1,)])
    def test_fused_scale_odd_shapes(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
        out = fused_scale(x, 1.7, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 1.7,
                                   rtol=1e-6)

    @pytest.mark.parametrize("shape", [(130,), (3, 77)])
    def test_fused_scale_odd_shapes_with_cast(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        out = fused_scale(x, 0.3, out_dtype=jnp.bfloat16, interpret=True)
        assert out.dtype == jnp.bfloat16 and out.shape == shape
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(x) * 0.3, rtol=1e-2,
                                   atol=1e-2)

    @pytest.mark.parametrize("t", [
        24,    # < one tile, multiple of 8: single whole-seq block
        48,    # not a multiple of the requested 32 block, still 8k
        136,   # > 128 but no 128-multiple divisor: dense fallback
        30,    # ragged (not even 8k): dense fallback
    ])
    def test_flash_attention_off_grid_seq_parity(self, t):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        shape = (2, t, 2, 16)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
        out = flash_attention(q, k, v, causal=True, block_q=32,
                              block_k=32, interpret=True)
        expected = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_flash_attention_off_grid_seq_grads(self):
        """The custom-vjp boundary must stay differentiable on fallback
        and shrunken-block shapes alike."""
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        shape = (1, 24, 2, 8)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=32, block_k=32,
                                           interpret=True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_flash_attention_bf16_off_grid(self):
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        shape = (1, 48, 2, 16)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks)
        out = flash_attention(q, k, v, causal=True, block_q=32,
                              block_k=32, interpret=True)
        expected = reference_attention(q.astype(jnp.float32),
                                       k.astype(jnp.float32),
                                       v.astype(jnp.float32), causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expected), rtol=0.05,
                                   atol=0.05)

    # -- expert dispatch at off-tile shapes (ISSUE 16 satellite):
    #    the fused a2a⊗expert-matmul ring through the FULL
    #    expert_parallel_ffn pipeline (routing, capacity, drops) at
    #    shapes the happy-path parity never touches

    def _expert_pair(self, t, d, e_total, world, capacity_factor,
                     dtype=jnp.float32, gate_w=None, seed=0):
        """(fused_y, unfused_y, fused_drop, unfused_drop) from the same
        tokens/router/experts on a ``world``-way ep mesh."""
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.parallel.expert import expert_parallel_ffn
        from horovod_tpu.parallel.mesh import make_parallel_mesh

        mesh = make_parallel_mesh(ep=world,
                                  devices=jax.devices("cpu")[:world])
        key = jax.random.PRNGKey(seed)
        x = jax.random.normal(key, (t, d)).astype(dtype)
        if gate_w is None:
            gate_w = jax.random.normal(jax.random.fold_in(key, 1),
                                       (d, e_total)).astype(dtype)
        e_local = e_total // world
        w = jax.random.normal(jax.random.fold_in(key, 2),
                              (world, e_local, d, d)).astype(dtype) * 0.3

        def f(x, gate_w, w):
            def expert_fn(buffers):
                return jnp.einsum("esd,edk->esk", buffers, w[0])

            def run(fused):
                y, dropped = expert_parallel_ffn(
                    x, gate_w, expert_fn, e_total,
                    capacity_factor=capacity_factor, fused=fused)
                return y, dropped[None]

            (yf, df), (yu, du) = run(True), run(False)
            return yf, yu, df, du

        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P(), P("ep")),
            out_specs=(P(), P(), P(), P()), check_vma=False))(
                x, gate_w, w)

    @pytest.mark.parametrize("t,d", [
        (13, 5),    # odd everything: capacity ceil(1.25*13/8) = 3
        (31, 7),    # prime token count, odd feature dim
    ])
    def test_expert_dispatch_off_tile_tokens(self, t, d):
        yf, yu, df, du = self._expert_pair(t, d, e_total=8, world=8,
                                           capacity_factor=1.25)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yu),
                                   rtol=1e-5, atol=1e-5)
        assert float(df[0]) == float(du[0])

    def test_expert_dispatch_capacity_overflow_drop_parity(self):
        """Over-capacity routing: the fused ring must drop EXACTLY the
        tokens the unfused path drops (same zero rows, same fraction)."""
        d, e_total = 4, 8
        # every token prefers expert 0 at cf=1.0 -> heavy dropping
        gate_w = jnp.zeros((d, e_total)).at[:, 0].set(10.0)
        yf, yu, df, du = self._expert_pair(
            24, d, e_total=e_total, world=8, capacity_factor=1.0,
            gate_w=gate_w, seed=1)
        assert float(df[0]) > 0.5
        assert float(df[0]) == float(du[0])
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yu),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(
            np.abs(np.asarray(yf)).sum(axis=1) == 0,
            np.abs(np.asarray(yu)).sum(axis=1) == 0)

    def test_expert_dispatch_one_expert_per_rank(self):
        """E == world degenerate ring: every hop carries exactly one
        expert's bucket."""
        yf, yu, df, du = self._expert_pair(16, 6, e_total=8, world=8,
                                           capacity_factor=2.0, seed=2)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yu),
                                   rtol=1e-5, atol=1e-5)
        assert float(df[0]) == float(du[0])

    def test_expert_dispatch_world_one(self):
        """ep extent 1: no wire at all — both schedules are the local
        expert call."""
        yf, yu, df, du = self._expert_pair(10, 4, e_total=4, world=1,
                                           capacity_factor=4.0, seed=3)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yu),
                                   rtol=1e-6, atol=1e-6)
        assert float(df[0]) == float(du[0])

    def test_expert_dispatch_bf16(self):
        yf, yu, df, du = self._expert_pair(
            16, 8, e_total=8, world=8, capacity_factor=8.0,
            dtype=jnp.bfloat16, seed=4)
        assert yf.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(yf, np.float32),
                                   np.asarray(yu, np.float32),
                                   rtol=5e-2, atol=5e-2)
        assert float(df[0]) == float(du[0])


class TestPallasMatmul:
    """Blocked Pallas matmul — the per-tile compute of the fused
    collective ops."""

    def test_tile_contract_shapes(self):
        from horovod_tpu.ops.pallas_kernels import pallas_matmul

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(16, 128), jnp.float32)
        w = jnp.asarray(rng.randn(128, 256), jnp.float32)
        out = pallas_matmul(x, w, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)

    def test_off_contract_falls_back(self):
        from horovod_tpu.ops.pallas_kernels import pallas_matmul

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(7, 33), jnp.float32)   # nothing tiles
        w = jnp.asarray(rng.randn(33, 19), jnp.float32)
        out = pallas_matmul(x, w, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_accumulates_fp32(self):
        from horovod_tpu.ops.pallas_kernels import pallas_matmul

        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(8, 128), jnp.bfloat16)
        w = jnp.asarray(rng.randn(128, 128), jnp.bfloat16)
        out = pallas_matmul(x, w, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=0.05, atol=0.05)


    def test_k_blocked_accumulation(self):
        """k spans several 512-blocks: the fp32 accumulator carries the
        partial sums across the sequential K axis of the grid."""
        from horovod_tpu.ops.pallas_kernels import pallas_matmul

        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(16, 1536), jnp.float32)
        w = jnp.asarray(rng.randn(1536, 128), jnp.float32)
        out = pallas_matmul(x, w, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-4)

    def test_grad_matches_jnp_dot(self):
        """jax.grad goes through the KERNEL (its custom VJP is two more
        kernel calls), not through a jnp fallback: off-TPU only
        interpret mode runs the kernel, and before the VJP existed
        autodiff of it raised "Linearization failed"."""
        from horovod_tpu.ops.pallas_kernels import pallas_matmul

        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(64, 1024), jnp.float32)
        w = jnp.asarray(rng.randn(1024, 256), jnp.float32)

        def loss(mm):
            return lambda x, w: jnp.sum(mm(x, w) ** 2)

        got = jax.grad(loss(lambda x, w: pallas_matmul(
            x, w, interpret=True)), (0, 1))(x, w)
        want = jax.grad(loss(jnp.dot), (0, 1))(x, w)
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-5, atol=1e-2)


class TestNoHiddenFallbackOnTpu:
    """On a TPU backend the kernels must not quietly become something
    else: interpreter mode raises, and a sequence no flash block fits
    says so.  The backend is faked — what is pinned is the decision."""

    def test_interpret_on_tpu_raises(self, monkeypatch):
        from horovod_tpu.ops import pallas_kernels as pk

        monkeypatch.setattr(pk, "_on_tpu", lambda: True)
        x = jnp.ones((8, 128), jnp.float32)
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            pk.pallas_matmul(x, jnp.ones((128, 128)), interpret=True)
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            q = jnp.ones((1, 128, 1, 8), jnp.float32)
            pk.flash_attention(q, q, q, interpret=True)

    def test_unblockable_seq_is_reported_once(self, monkeypatch):
        from horovod_tpu.ops import pallas_kernels as pk

        calls = []
        monkeypatch.setattr(pk, "_on_tpu", lambda: True)
        monkeypatch.setattr(pk.hvd_logging, "warning",
                            lambda msg, *a: calls.append(msg % a))
        monkeypatch.setattr(pk, "_warned_unblocked", set())
        q = jnp.ones((1, 200, 2, 8), jnp.float32)    # 200: no block fits
        for _ in range(2):
            out = pk.flash_attention(q, q, q, causal=True)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(reference_attention(q, q, q, causal=True)),
            rtol=1e-6, atol=1e-6)
        assert len(calls) == 1 and "(1, 200, 2, 8)" in calls[0], calls

    def test_probes_do_not_swallow_backend_errors(self, monkeypatch):
        import horovod_tpu as hvd
        from horovod_tpu.ops import pallas_kernels as pk

        def broken():
            raise RuntimeError("backend failed to initialise")

        monkeypatch.setattr(jax, "devices", broken)
        with pytest.raises(RuntimeError, match="failed to initialise"):
            pk._on_tpu()
        with pytest.raises(RuntimeError, match="failed to initialise"):
            hvd.tpu_available()


class TestFusedMatmulCollectives:
    """Tile-fused matmul⊗collective ring kernels vs the unfused
    formulation they replace — numerics pinned per the
    graceful-degradation contract (ISSUE 9 tentpole)."""

    W = 8

    def _mesh(self):
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices("cpu")[:self.W])
        return Mesh(devs.reshape(self.W), ("tp",))

    def _run(self, fn, *args, out_specs=None):
        from jax.sharding import PartitionSpec as P

        sm = jax.jit(jax.shard_map(
            fn, mesh=self._mesh(), in_specs=(P(),) * len(args),
            out_specs=out_specs if out_specs is not None else P(),
            check_vma=False))
        return sm(*args)

    def test_matmul_reducescatter_matches_unfused(self):
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops.pallas_kernels import matmul_reducescatter

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        w = jnp.asarray(rng.randn(16, 8), jnp.float32)

        def f(x, w):
            fused = matmul_reducescatter(x, w, "tp", fused=True)
            ref = matmul_reducescatter(x, w, "tp", fused=False)
            return fused, ref

        fused, ref = self._run(f, x, w, out_specs=(P("tp"), P("tp")))
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # closed form: replicated inputs psum W identical contributions
        np.testing.assert_allclose(np.asarray(ref).reshape(64, 8),
                                   self.W * np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)

    def test_allgather_matmul_matches_unfused(self):
        from horovod_tpu.ops.pallas_kernels import allgather_matmul

        rng = np.random.RandomState(1)
        shards = jnp.asarray(rng.randn(self.W, 4, 16), jnp.float32)
        w = jnp.asarray(rng.randn(16, 8), jnp.float32)

        def f(shards, w):
            from jax import lax

            mine = jnp.take(shards, lax.axis_index("tp"), axis=0)
            fused = allgather_matmul(mine, w, "tp", fused=True)
            ref = allgather_matmul(mine, w, "tp", fused=False)
            return fused, ref

        from jax.sharding import PartitionSpec as P

        fused, ref = self._run(f, shards, w, out_specs=(P(), P()))
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
        expect = np.asarray(shards).reshape(self.W * 4, 16) @ \
            np.asarray(w)
        np.testing.assert_allclose(np.asarray(ref), expect, rtol=1e-4,
                                   atol=1e-4)

    def test_bf16_ring_accumulates_fp32(self):
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops.pallas_kernels import matmul_reducescatter

        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(32, 16), jnp.bfloat16)
        w = jnp.asarray(rng.randn(16, 8), jnp.bfloat16)

        def f(x, w):
            return matmul_reducescatter(x, w, "tp", fused=True)

        out = self._run(f, x, w, out_specs=P("tp"))
        assert out.dtype == jnp.bfloat16
        ref = self.W * (np.asarray(x, np.float32) @
                        np.asarray(w, np.float32))
        np.testing.assert_allclose(
            np.asarray(out, np.float32).reshape(32, 8), ref,
            rtol=0.1, atol=0.5)

    def test_shape_validation(self):
        from horovod_tpu.ops.pallas_kernels import (
            allgather_matmul,
            matmul_reducescatter,
        )

        def bad_rows(x, w):
            return matmul_reducescatter(x, w, "tp")

        def bad_rank(x, w):
            return allgather_matmul(x[None], w, "tp")

        x = jnp.zeros((30, 16))     # 30 % 8 != 0
        w = jnp.zeros((16, 8))
        with pytest.raises(ValueError, match="divisible"):
            self._run(bad_rows, x, w)
        with pytest.raises(ValueError, match="2-D"):
            self._run(bad_rank, jnp.zeros((8, 16)), w)

    def test_resolve_modes(self):
        from horovod_tpu.ops.pallas_kernels import (
            resolve_fused_collectives,
        )

        assert resolve_fused_collectives("on") is True
        assert resolve_fused_collectives("off") is False
        # auto = TPU only; this suite runs the CPU twin
        assert resolve_fused_collectives("auto") is False
        with pytest.raises(ValueError, match="fused_collectives"):
            resolve_fused_collectives("maybe")

    def test_fused_launch_counter(self):
        from horovod_tpu import telemetry
        from horovod_tpu.ops.pallas_kernels import matmul_reducescatter

        telemetry.enable()
        try:
            before = telemetry.value(
                "hvd_pallas_fused_launches_total",
                kernel="matmul_reducescatter")

            def f(x, w):
                return matmul_reducescatter(x, w, "tp", fused=True)

            from jax.sharding import PartitionSpec as P

            self._run(f, jnp.zeros((16, 8)), jnp.zeros((8, 4)),
                      out_specs=P("tp"))
            after = telemetry.value(
                "hvd_pallas_fused_launches_total",
                kernel="matmul_reducescatter")
            assert after > before
        finally:
            telemetry.disable()


class TestFusedExpertDispatch:
    """``a2a ⊗ expert-matmul`` fused dispatch/combine ring vs the
    unfused all_to_all formulation it replaces (ISSUE 16 tentpole):
    identical tokens, drops, outputs and grads — only the schedule
    differs."""

    W = 8

    def _mesh(self, world=None):
        from jax.sharding import Mesh

        world = world or self.W
        devs = np.asarray(jax.devices("cpu")[:world])
        return Mesh(devs.reshape(world), ("ep",))

    @staticmethod
    def _expert_mlp(w1, w2):
        """Token-wise gelu MLP over an (e_local, slots, d) buffer —
        the contract expert_alltoall_ffn requires."""
        def expert_fn(t):
            h = jnp.einsum("ecd,edf->ecf", t, w1)
            return jnp.einsum("ecf,efd->ecd", jax.nn.gelu(h), w2)

        return expert_fn

    def _inputs(self, world, e_local=2, cap=3, d=4, f=8,
                dtype=jnp.float32, seed=0):
        rng = np.random.RandomState(seed)
        disp = jnp.asarray(
            rng.standard_normal((world, world, e_local, cap, d)), dtype)
        w1 = jnp.asarray(
            rng.standard_normal((world, e_local, d, f)) * 0.3, dtype)
        w2 = jnp.asarray(
            rng.standard_normal((world, e_local, f, d)) * 0.3, dtype)
        return disp, w1, w2

    def _pair(self, disp, w1, w2, world):
        """Run the fused ring and its unfused oracle over the same
        per-rank dispatch buffers + per-rank expert weights."""
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops.pallas_kernels import expert_alltoall_ffn

        def f(disp, w1, w2):
            expert_fn = self._expert_mlp(w1[0], w2[0])
            fused = expert_alltoall_ffn(disp[0], expert_fn, "ep",
                                        fused=True)
            ref = expert_alltoall_ffn(disp[0], expert_fn, "ep",
                                      fused=False)
            return fused[None], ref[None]

        return jax.jit(jax.shard_map(
            f, mesh=self._mesh(world),
            in_specs=(P("ep"), P("ep"), P("ep")),
            out_specs=(P("ep"), P("ep")), check_vma=False))(disp, w1, w2)

    def test_ring_matches_unfused_alltoall(self):
        world = self.W
        disp, w1, w2 = self._inputs(world)
        fused, ref = self._pair(disp, w1, w2, world)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # closed form: out[r, q, e, c] = expert (q, e)'s MLP applied to
        # the tile rank r addressed to it — both schedules must hit it
        h = jnp.einsum("rqecd,qedf->rqecf", disp, w1)
        expect = jnp.einsum("rqecf,qefd->rqecd", jax.nn.gelu(h), w2)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match_unfused(self):
        """Differentiable end-to-end: the ring transposes must produce
        the same dx/dw1/dw2 as the all_to_all formulation."""
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops.pallas_kernels import expert_alltoall_ffn

        world = self.W
        disp, w1, w2 = self._inputs(world, seed=1)
        mesh = self._mesh(world)

        def make_loss(fused):
            def f(disp, w1, w2):
                expert_fn = self._expert_mlp(w1[0], w2[0])
                out = expert_alltoall_ffn(disp[0], expert_fn, "ep",
                                          fused=fused)
                return lax.psum(jnp.sum(out ** 2), "ep")

            sm = jax.shard_map(
                f, mesh=mesh, in_specs=(P("ep"), P("ep"), P("ep")),
                out_specs=P(), check_vma=False)
            return jax.jit(jax.grad(sm, argnums=(0, 1, 2)))

        gf = make_loss(True)(disp, w1, w2)
        gu = make_loss(False)(disp, w1, w2)
        for a, b in zip(gf, gu):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_bf16_parity(self):
        world = self.W
        disp, w1, w2 = self._inputs(world, dtype=jnp.bfloat16, seed=2)
        fused, ref = self._pair(disp, w1, w2, world)
        assert fused.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(fused, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_single_local_expert_ring(self):
        """E == world: one expert per rank — the tightest ring (every
        tile is one expert's bucket)."""
        world = self.W
        disp, w1, w2 = self._inputs(world, e_local=1, cap=2, seed=3)
        fused, ref = self._pair(disp, w1, w2, world)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_world_one_degenerate_ring(self):
        """A 1-rank axis has no wire: both schedules reduce to one
        local expert_fn call."""
        disp, w1, w2 = self._inputs(1, e_local=4, seed=4)
        fused, ref = self._pair(disp, w1, w2, 1)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_shape_validation(self):
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops.pallas_kernels import expert_alltoall_ffn

        def run(fn, x):
            return jax.jit(jax.shard_map(
                fn, mesh=self._mesh(), in_specs=(P(),),
                out_specs=P(), check_vma=False))(x)

        with pytest.raises(ValueError, match="dispatch buffer"):
            run(lambda x: expert_alltoall_ffn(x, lambda t: t, "ep"),
                jnp.zeros((8, 2, 3)))
        with pytest.raises(ValueError, match="dim 0"):
            run(lambda x: expert_alltoall_ffn(x, lambda t: t, "ep"),
                jnp.zeros((4, 2, 3, 4)))

    def test_fused_launch_counter(self):
        from horovod_tpu import telemetry

        telemetry.enable()
        try:
            before = telemetry.value(
                "hvd_pallas_fused_launches_total", kernel="a2a_matmul")
            disp, w1, w2 = self._inputs(self.W, seed=5)
            self._pair(disp, w1, w2, self.W)
            after = telemetry.value(
                "hvd_pallas_fused_launches_total", kernel="a2a_matmul")
            assert after > before
        finally:
            telemetry.disable()


# ---------------------------------------------------------------------------
# the Mamba-2 chunked scan
# ---------------------------------------------------------------------------

def _ssd_inputs(seed, chunks, groups, heads, dtype=jnp.float32, bsz=2,
                p=64, n=128, q=128, dt_shift=-3.0, a=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    t, h = chunks * q, groups * heads
    x = jax.random.normal(k[0], (bsz, t, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) + dt_shift)
    if a is None:
        a = -jnp.exp(jax.random.normal(k[2], (h,)))
    b = (0.3 * jax.random.normal(k[3], (bsz, t, groups, n))).astype(dtype)
    c = (0.3 * jax.random.normal(k[4], (bsz, t, groups, n))).astype(dtype)
    return x, dt, a, b, c


def _ssd_recurrence(x, dt, a, b, c, log_decay=None):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t b_t (x) x_t``, ``y_t = c_t.h_t``
    one step at a time in fp32; ``log_decay`` (B, T, H) stands in for
    ``dt a`` where a test rounds it."""
    f32 = jnp.float32
    x, b, c = (v.astype(f32) for v in (x, b, c))
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))
    log_decay = dt * a if log_decay is None else log_decay

    def one(state, at):
        x_t, dt_t, ld_t, b_t, c_t = at
        state = jnp.exp(ld_t)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(one, jnp.zeros((bsz, h, p, n)), tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, log_decay, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _rel(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _value_and_grads(f, args):
    def scalar(*a):
        return jnp.sum(jnp.sin(f(*a)))
    y = jax.jit(f)(*args)
    return (y,) + jax.jit(jax.grad(scalar, argnums=range(5)))(*args)


def _has_pallas_call(f, args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


class TestSsdScan:
    """``ssd_scan`` interpreted on the CPU at shapes its kernels take
    (chunk 128, state 128, heads of 64) against the einsum form and
    against the recurrence itself."""

    @pytest.mark.parametrize("groups,heads", [(1, 2), (2, 4)])
    @pytest.mark.parametrize("chunks", [1, 2, 5])
    def test_values_and_five_gradients_in_fp32(self, chunks, groups, heads):
        from horovod_tpu.ops.pallas_kernels import ssd_chunked, ssd_scan

        args = _ssd_inputs(chunks, chunks, groups, heads)

        def kernel(*a):
            return ssd_scan(*a, 128, interpret=True)
        assert _has_pallas_call(kernel, args)
        got = _value_and_grads(kernel, args)
        einsum = _value_and_grads(lambda *a: ssd_chunked(*a, 128), args)
        stepwise = _value_and_grads(_ssd_recurrence, args)
        for name, u, v, w in zip(("y", "dx", "ddt", "da", "db", "dc"),
                                 got, einsum, stepwise):
            assert u.shape == v.shape and u.dtype == v.dtype, name
            # da: one number a head, what is left when every position's
            # terms of both signs are added up — fp32's own noise there
            # is a few 1e-5 between any two orders of summation
            limit = 1e-4 if name == "da" else 1e-5
            assert _rel(u, v) <= limit, (name, _rel(u, v))
            assert _rel(u, w) <= 1e-4, (name, _rel(u, w))

    def test_bf16_operands_stay_within_bf16_of_the_fp32_oracle(self):
        """Rounded where the einsum form rounds (the decayed C B^T, x dt,
        the started state, B, C), added up in fp32: no further from the
        fp32 oracle than bf16's 2^-8, as the einsum form on the same
        operands."""
        from horovod_tpu.ops.pallas_kernels import ssd_chunked, ssd_scan

        args = _ssd_inputs(7, 2, 2, 4, jnp.bfloat16)
        got = _value_and_grads(
            lambda *a: ssd_scan(*a, 128, interpret=True), args)
        einsum = _value_and_grads(
            lambda *a: ssd_chunked(*a, 128, jnp.bfloat16), args)
        oracle = _value_and_grads(
            lambda *a: ssd_chunked(*a, 128, jnp.float32), args)
        assert got[0].dtype == jnp.float32          # y, of bf16 operands
        for name, u, v, w in zip(("y", "dx", "ddt", "da", "db", "dc"),
                                 got, einsum, oracle):
            assert u.dtype == w.dtype, name         # dx, db, dc bf16
            assert _rel(u, w) <= 2 ** -8, (name, _rel(u, w))
            assert _rel(u, w) <= 1.5 * _rel(v, w), (name, _rel(v, w))

    def test_the_decay_stays_fp32(self):
        """Five chunks at ``dt a`` down to -0.4 a step: a chunk's
        log-decay prefix reaches -30, where bf16 is 0.125–0.25 apart.
        Two neighbours' decay ``exp(cum_l - cum_s)`` is then wrong by a
        tenth if the prefix is rounded — the recurrence fed such decays
        misses bf16's limit several times over — and the kernel, on bf16
        operands, does not."""
        from horovod_tpu.ops.pallas_kernels import ssd_scan

        a = -jnp.array([0.5, 1.0, 2.0, 4.0])
        args = _ssd_inputs(11, 5, 2, 2, jnp.bfloat16, dt_shift=-2.0, a=a)
        x, dt, _, b, c = args
        want = _ssd_recurrence(*args)
        got = jax.jit(lambda *v: ssd_scan(*v, 128, interpret=True))(*args)
        assert _rel(got, want) <= 2 ** -8, _rel(got, want)
        steps = (dt * a).reshape(2, 5, 128, 4)
        prefix = jnp.cumsum(steps, axis=2)
        assert float(prefix.min()) < -30
        rounded = prefix.astype(jnp.bfloat16).astype(jnp.float32)
        rounded = rounded - jnp.pad(rounded, [(0, 0), (0, 0), (1, 0),
                                              (0, 0)])[:, :, :-1]
        lossy = _ssd_recurrence(*args, log_decay=rounded.reshape(dt.shape))
        assert _rel(lossy, want) > 4 * 2 ** -8, _rel(lossy, want)
        # and so do the gradients that pass through the decays
        grads = jax.jit(jax.grad(
            lambda *v: jnp.sum(jnp.sin(ssd_scan(*v, 128, interpret=True))),
            argnums=(1, 2)))(*args)
        wanted = jax.jit(jax.grad(
            lambda *v: jnp.sum(jnp.sin(_ssd_recurrence(*v))),
            argnums=(1, 2)))(*args)
        for u, v in zip(grads, wanted):
            assert _rel(u, v) <= 2 ** -7, _rel(u, v)

    @pytest.mark.parametrize("seq,chunk,heads,p,n", [
        (200, 128, 2, 64, 128),     # no whole number of chunks
        (128, 64, 2, 64, 128),      # a chunk that is no multiple of 128
        (128, 128, 2, 64, 64),      # nor the state
        (128, 128, 2, 24, 128),     # nor a head's rows of 16
    ])
    def test_shapes_without_a_kernel_take_the_einsum_form(self, seq, chunk,
                                                          heads, p, n):
        from horovod_tpu.ops.pallas_kernels import ssd_chunked, ssd_scan

        k = jax.random.split(jax.random.PRNGKey(seq + chunk), 5)
        args = (jax.random.normal(k[0], (2, seq, heads, p)),
                jax.nn.softplus(jax.random.normal(k[1], (2, seq, heads))),
                -jnp.exp(jax.random.normal(k[2], (heads,))),
                jax.random.normal(k[3], (2, seq, 1, n)),
                jax.random.normal(k[4], (2, seq, 1, n)))

        def scan(*a):
            return ssd_scan(*a, chunk, interpret=True)
        assert not _has_pallas_call(scan, args)
        for u, v in zip(_value_and_grads(scan, args), _value_and_grads(
                lambda *a: ssd_chunked(*a, chunk), args)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_off_a_tpu_without_the_interpreter_it_is_the_einsum_form(self):
        from horovod_tpu.ops.pallas_kernels import ssd_scan

        args = _ssd_inputs(0, 1, 1, 2)
        assert not _has_pallas_call(lambda *a: ssd_scan(*a, 128), args)

    def test_interpret_on_a_tpu_raises(self, monkeypatch):
        from horovod_tpu.ops import pallas_kernels as pk

        monkeypatch.setattr(pk, "_on_tpu", lambda: True)
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            pk.ssd_scan(*_ssd_inputs(0, 1, 1, 2), 128, interpret=True)
