"""Ring-flash context parallelism (ISSUE 17): the fused sp-ring ⊗
flash attention kernel, its layouts and causal launch schedule, the
NaN hazard pins, and sp as an end-to-end plan axis through
``DistributedTrainStep``.

Numerics oracle pattern (test_parallel.py style): the fused ring runs
in Pallas interpreter mode on the virtual 8-device CPU mesh and is
pinned against the dense single-device reference AND the jnp
log-sum-exp ring — same math, three formulations.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops import pallas_kernels as PK
from horovod_tpu.parallel import (
    make_parallel_mesh,
    ring_attention,
    ulysses_attention,
)
from horovod_tpu.parallel.ring_attention import reference_attention


def sp_mesh(sp):
    return make_parallel_mesh(sp=sp, devices=jax.devices("cpu")[:sp])


def make_qkv(b=2, t=32, h=4, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def run_ring(q, k, v, sp, causal, layout="contiguous", fused=True,
             block=512):
    """The q/k/v through a shard_map'd ring over an sp-way mesh.

    ``fused=True`` forces the ring-flash path (Pallas interpreter mode
    on CPU); ``fused=False`` forces the jnp log-sum-exp ring.  Under
    ``zigzag`` the GLOBAL tensors are permuted into the zigzag shard
    order on the way in and un-permuted on the way out, so callers
    always compare in natural sequence order.
    """
    mesh = sp_mesh(sp)
    spec = P(None, "sp", None, None)
    t = q.shape[1]
    if layout == "zigzag":
        sigma = np.asarray(PK.zigzag_sequence_indices(sp, t))
        inv = np.argsort(sigma)
        q, k, v = (x[:, sigma] for x in (q, k, v))

    def f(q_, k_, v_):
        return ring_attention(q_, k_, v_, "sp", causal=causal,
                              fused=fused, layout=layout,
                              block_q=block, block_k=block,
                              interpret=True)

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False))(q, k, v)
    if layout == "zigzag":
        out = out[:, inv]
    return out


class TestRingLayouts:
    def test_contiguous_positions(self):
        for r in range(4):
            np.testing.assert_array_equal(
                np.asarray(PK.ring_layout_positions(r, 4, 8,
                                                    "contiguous")),
                np.arange(r * 8, (r + 1) * 8))

    def test_zigzag_positions_pair_early_and_late(self):
        # rank r holds the r-th and (2·world−1−r)-th half-chunks
        w, t = 4, 8
        half = t // 2
        for r in range(w):
            pos = np.asarray(PK.ring_layout_positions(r, w, t, "zigzag"))
            np.testing.assert_array_equal(
                pos[:half], np.arange(r * half, (r + 1) * half))
            late = 2 * w - 1 - r
            np.testing.assert_array_equal(
                pos[half:], np.arange(late * half, (late + 1) * half))

    def test_zigzag_positions_cover_the_sequence(self):
        w, t = 4, 6
        allpos = np.concatenate([
            np.asarray(PK.ring_layout_positions(r, w, t, "zigzag"))
            for r in range(w)])
        assert sorted(allpos.tolist()) == list(range(w * t))

    def test_zigzag_sigma_matches_positions(self):
        # the host-side permutation IS the concatenated shard layout:
        # shard r of x[:, sigma] holds exactly ring_layout_positions(r)
        w, t = 4, 8
        sigma = np.asarray(PK.zigzag_sequence_indices(w, w * t))
        stacked = np.concatenate([
            np.asarray(PK.ring_layout_positions(r, w, t, "zigzag"))
            for r in range(w)])
        np.testing.assert_array_equal(sigma, stacked)

    def test_unknown_layout_raises(self):
        with pytest.raises(ValueError, match="layout"):
            PK.ring_layout_positions(0, 4, 8, "striped")


class TestRingStepSchedule:
    def test_contiguous_causal_census(self):
        s = PK.ring_step_schedule(4, causal=True, layout="contiguous")
        assert s["launches"] == 10
        assert s["skipped"] == 6
        assert s["skipped_by_rank"] == (3, 2, 1, 0)

    @pytest.mark.parametrize("w", [2, 4, 8])
    def test_contiguous_causal_skips_triangle(self, w):
        s = PK.ring_step_schedule(w, causal=True, layout="contiguous")
        assert s["skipped"] == w * (w - 1) // 2
        assert s["launches"] + s["skipped"] == w * w

    @pytest.mark.parametrize("w", [2, 4, 8])
    def test_zigzag_causal_never_skips(self, w):
        # no (q chunk, k/v chunk) pair is ever fully in the future —
        # the mask work rebalances instead of whole launches dropping
        s = PK.ring_step_schedule(w, causal=True, layout="zigzag")
        assert s["launches"] == w * w
        assert s["skipped_by_rank"] == (0,) * w

    @pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
    def test_non_causal_never_skips(self, layout):
        s = PK.ring_step_schedule(4, causal=False, layout=layout)
        assert (s["launches"], s["skipped"]) == (16, 0)

    def test_unknown_layout_raises(self):
        with pytest.raises(ValueError, match="layout"):
            PK.ring_step_schedule(4, layout="striped")


class TestRingFlashParity:
    """The tentpole pin: fused ring-flash == dense reference == jnp
    ring, logits and grads, causal and not, both layouts, at
    tile-straddling shard lengths."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
    @pytest.mark.parametrize("sp,t", [(2, 64), (4, 128), (4, 96)])
    def test_matches_dense(self, causal, layout, sp, t):
        q, k, v = make_qkv(t=t)
        out = run_ring(q, k, v, sp, causal, layout=layout, fused=True)
        expected = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_fused_matches_jnp_ring(self, causal):
        q, k, v = make_qkv(t=64)
        fused = run_ring(q, k, v, 4, causal, fused=True)
        unfused = run_ring(q, k, v, 4, causal, fused=False)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(unfused),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("t_local", [8, 40])
    def test_tile_straddling_shard_lengths(self, t_local):
        # shard lengths off the 512/128 tile grid still take the fused
        # path (fit_flash_block degrades the block, never the math)
        sp = 2
        q, k, v = make_qkv(b=1, t=sp * t_local, h=2, d=8)
        out = run_ring(q, k, v, sp, True, fused=True)
        expected = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
    def test_grad_matches_dense(self, layout):
        sp, t = 4, 32
        q, k, v = make_qkv(b=1, t=t, h=2, d=8)
        mesh = sp_mesh(sp)
        spec = P(None, "sp", None, None)
        if layout == "zigzag":
            sigma = np.asarray(PK.zigzag_sequence_indices(sp, t))
        else:
            sigma = np.arange(t)

        def ring_loss(q, k, v):
            smapped = jax.shard_map(
                lambda q_, k_, v_: ring_attention(
                    q_, k_, v_, "sp", causal=True, fused=True,
                    layout=layout, interpret=True),
                mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False)
            return jnp.sum(smapped(q[:, sigma], k[:, sigma],
                                   v[:, sigma]) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(reference_attention(q, k, v,
                                               causal=True) ** 2)

        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for gr, gd, name in zip(g_ring, g_dense, "qkv"):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")

    def test_fused_grad_matches_jnp_ring_grad(self):
        sp, t = 2, 64
        q, k, v = make_qkv(b=1, t=t, h=2, d=8)
        mesh = sp_mesh(sp)
        spec = P(None, "sp", None, None)

        def grads(fused):
            def loss(q, k, v):
                smapped = jax.shard_map(
                    lambda q_, k_, v_: ring_attention(
                        q_, k_, v_, "sp", causal=True, fused=fused,
                        interpret=True),
                    mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                    check_vma=False)
                return jnp.sum(smapped(q, k, v) ** 2)

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        for gf, gu, name in zip(grads(True), grads(False), "qkv"):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gu),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")


class TestRingNaNGuard:
    """ISSUE 17 satellite: a causal ring step whose visiting K/V block
    is entirely in the future contributes softmax over an all-masked
    row — both formulations must emit exact zeros there, never NaN
    (the lse=-inf / l=0 hazard)."""

    @pytest.mark.parametrize("world,t", [(8, 8), (8, 16), (4, 4)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_jnp_ring_tiny_shards_finite(self, world, t, causal):
        # t_local down to ONE query per shard: on rank 0 every visiting
        # block except its own is fully masked under causal
        q, k, v = make_qkv(b=1, t=t, h=2, d=8)
        out = run_ring(q, k, v, world, causal, fused=False)
        assert np.isfinite(np.asarray(out)).all()
        expected = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_fused_ring_skipped_steps_finite(self):
        # contiguous causal at sp=4: rank 0 skips 3 of its 4 launches
        # (ring_step_schedule) — the identity carry must keep the
        # accumulator at the finite sentinel, not -inf
        q, k, v = make_qkv(b=1, t=32, h=2, d=8)
        out = run_ring(q, k, v, 4, True, fused=True)
        assert np.isfinite(np.asarray(out)).all()
        expected = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_fused_single_query_shards_finite(self):
        q, k, v = make_qkv(b=1, t=8, h=2, d=8)
        out = run_ring(q, k, v, 8, True, fused=True)
        assert np.isfinite(np.asarray(out)).all()
        expected = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)


class TestUlyssesOddSeqs:
    """ISSUE 17 satellite: Ulysses at sequence lengths off the flash
    tile grid (24, 136 over 8 shards -> t_local 3 and 17) — parity and
    grads against dense, plus the long-context ring-vs-ulysses pin
    where the dense (T, T) oracle would not fit."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("t", [24, 136])
    def test_matches_dense(self, causal, t):
        q, k, v = make_qkv(t=t, h=8)
        mesh = sp_mesh(8)
        spec = P(None, "sp", None, None)
        out = jax.jit(jax.shard_map(
            lambda q_, k_, v_: ulysses_attention(q_, k_, v_, "sp",
                                                 causal=causal),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False))(q, k, v)
        expected = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_dense_odd_seq(self):
        q, k, v = make_qkv(b=1, t=24, h=8, d=8)
        mesh = sp_mesh(8)
        spec = P(None, "sp", None, None)

        def uly_loss(q, k, v):
            smapped = jax.shard_map(
                lambda q_, k_, v_: ulysses_attention(q_, k_, v_, "sp",
                                                     causal=True),
                mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False)
            return jnp.sum(smapped(q, k, v) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(reference_attention(q, k, v,
                                               causal=True) ** 2)

        g_u = jax.jit(jax.grad(uly_loss, argnums=(0, 1, 2)))(q, k, v)
        g_d = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for gu, gd in zip(g_u, g_d):
            np.testing.assert_allclose(np.asarray(gu), np.asarray(gd),
                                       rtol=1e-4, atol=1e-4)

    def test_ring_vs_ulysses_long_context(self):
        # seq 4104 = 4096 + 8: t_local 513 straddles every flash tile;
        # no dense oracle (the (T, T) scores would be ~540 MB) — the
        # two independent exact formulations must agree on their own
        t = 4104
        q, k, v = make_qkv(b=1, t=t, h=8, d=8)
        mesh = sp_mesh(8)
        spec = P(None, "sp", None, None)

        def run(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False))(q, k, v)

        ring = run(lambda q_, k_, v_: ring_attention(
            q_, k_, v_, "sp", causal=True, fused=False))
        uly = run(lambda q_, k_, v_: ulysses_attention(
            q_, k_, v_, "sp", causal=True))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(uly),
                                   rtol=2e-5, atol=2e-5)


class TestTrainStepSp:
    """sp as a real plan axis: ``DistributedTrainStep(plan="dp=4,sp=2",
    mode="shard_map")`` trains the ring-attention LM and its losses and
    parameters track the dp-only dense twin on the same global batch."""

    LAYERS, D, HEADS, VOCAB, T = 1, 32, 4, 64, 32

    def _cfg(self, impl):
        from horovod_tpu.models import TransformerConfig

        return TransformerConfig(
            vocab_size=self.VOCAB, num_layers=self.LAYERS,
            num_heads=self.HEADS, d_model=self.D, d_ff=4 * self.D,
            max_seq_len=self.T, dtype=jnp.float32,
            attention_impl=impl)

    def _train(self, plan, impl, batch_rows, steps=3):
        import dataclasses

        from horovod_tpu.models import TransformerLM

        cfg = self._cfg(impl)
        model = TransformerLM(cfg)
        init_model = model if impl == "dense" else TransformerLM(
            dataclasses.replace(cfg, attention_impl="dense"))
        sp = 2 if "sp" in plan else 1

        def loss_fn(params, batch):
            kwargs = {}
            if sp > 1:
                t_local = batch["inputs"].shape[1]
                kwargs["positions"] = (lax.axis_index("sp") * t_local
                                       + jnp.arange(t_local))
            logits = model.apply(params, batch["inputs"], **kwargs)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["labels"]).mean()

        step = hvd.DistributedTrainStep(loss_fn, optax.adamw(1e-2),
                                        plan=plan, mode="shard_map")
        variables = jax.jit(init_model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, self.T), jnp.int32))
        params, opt_state = step.init(variables)
        batch = step.shard_batch({
            "inputs": jnp.asarray(batch_rows[:, :-1], jnp.int32),
            "labels": jnp.asarray(batch_rows[:, 1:], jnp.int32)})
        losses = []
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
        assert step.plan.sp == sp
        return jax.device_get(params), losses

    def test_sp_plan_matches_dense_twin(self, hvd_runtime):
        # 4 unique sequences; the dp=8 dense twin sees them twice so
        # both plans optimize the identical global objective
        rng = np.random.RandomState(0)
        rows4 = rng.randint(0, self.VOCAB, (4, self.T + 1))
        rows8 = np.tile(rows4, (2, 1))
        p_sp, l_sp = self._train("dp=4,sp=2", "ring", rows4)
        p_dense, l_dense = self._train("dp=8", "dense", rows8)
        assert np.isfinite(l_sp).all() and np.isfinite(l_dense).all()
        np.testing.assert_allclose(l_sp, l_dense, rtol=2e-4, atol=2e-4)
        flat_sp = jax.tree_util.tree_leaves(p_sp)
        flat_dense = jax.tree_util.tree_leaves(p_dense)
        for a, b in zip(flat_sp, flat_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_shard_map_accepts_sp_but_not_tp(self, hvd_runtime):
        def loss_fn(params, batch):
            return jnp.sum(params["w"] * batch)

        step = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                        plan="dp=4,sp=2",
                                        mode="shard_map")
        assert (step._sp, step._sp_axis) == (2, "sp")
        assert step.plan.sp == 2
        with pytest.raises(ValueError, match="model axes"):
            hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                     plan="dp=4,tp=2",
                                     mode="shard_map")
