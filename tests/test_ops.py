"""Numerics tests for Pallas kernels (interpret mode on the CPU platform)
vs plain-XLA oracles. Reference analogue:
atorch/tests/test_modules/test_flash_attn.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.flash_attention import (
    KERNEL_DKV,
    KERNEL_DQ,
    KERNEL_FWD,
    causal_plan,
    fit_block,
    flash_attention,
    reference_attention,
)
from dlrover_tpu.ops.norms import (
    fused_rms_norm,
    mesh_rms_norm,
    reference_rms_norm,
)


def _qkv(batch=1, heads=2, kv_heads=None, seq=128, dim=64, dtype=jnp.float32,
         seed=0):
    kv_heads = kv_heads or heads
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (batch, heads, seq, dim), dtype)
    k = jax.random.normal(keys[1], (batch, kv_heads, seq, dim), dtype)
    v = jax.random.normal(keys[2], (batch, kv_heads, seq, dim), dtype)
    return q, k, v


class TestFlashAttentionForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(seq=256, dim=64)
        out = flash_attention(q, k, v, causal, None, 128, 128)
        ref = reference_attention(q, k, v, causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_uneven_seq_blocks(self):
        # seq not a multiple of block size exercises padding-free path
        q, k, v = _qkv(seq=128, dim=64)
        out = flash_attention(q, k, v, True, None, 64, 32)
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_fit_block_always_divides(self):
        """Requested blocks must be rounded down to a divisor of seq —
        on real TPU an out-of-bounds block reads undefined data and the
        dk/dv accumulation would fold it into valid gradients."""
        for n in [64, 128, 192, 1000, 1536, 2048, 4096, 7]:
            for req in [128, 256, 1024]:
                b = fit_block(n, req)
                assert n % b == 0 and b <= max(req, 1)
        assert fit_block(2048, 1024) == 1024
        assert fit_block(1536, 1024) == 768   # 128-aligned divisor
        assert fit_block(1000, 256) == 250    # no aligned divisor

    def test_indivisible_seq_matches_reference(self):
        # 192 % 128 != 0: the default 1024 request must shrink to a
        # divisor, not pad
        q, k, v = _qkv(seq=192, dim=64)
        out = flash_attention(q, k, v, True)
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        q, k, v = _qkv(heads=4, kv_heads=2, seq=128, dim=64)
        out = flash_attention(q, k, v, True, None, 64, 64)
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        q, k, v = _qkv(seq=128, dim=64, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, True, None, 64, 64)
        ref = reference_attention(q, k, v, True)
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32),
            atol=2e-2, rtol=2e-2,
        )


class TestFlashAttentionBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v = _qkv(seq=128, dim=64)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, None, 64, 64)
                           ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    @pytest.mark.parametrize("seq_q,seq_k", [(64, 256), (256, 64)])
    def test_cross_length_grads(self, seq_q, seq_k):
        # seq_k > seq_q regression: the dkv DMA-dedupe clamp must stay
        # within q's block range even for trailing kv blocks that have
        # no contributing q block (OOB block indices DMA undefined
        # memory on real TPU; interpret mode zero-pads, so this guards
        # the index math itself).
        q, _, _ = _qkv(seq=seq_q, dim=64)
        _, k, v = _qkv(seq=seq_k, dim=64, seed=1)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, None, 64, 64)
                           ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, True) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_gqa_grads(self):
        q, k, v = _qkv(heads=4, kv_heads=2, seq=64, dim=64)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, None, 64, 64) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, True) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


# (seq_q, seq_k, block_q, block_k, heads, kv_heads, dim, causal, grain):
# the last is the sub-tile grain `causal_plan` must choose for the shape
# (0 = today's whole-block path), so each case is known to hit its path.
_SUBTILE_CASES = {
    "cell_2048_b1024": (2048, 2048, 1024, 1024, 2, 2, 128, True, 128),
    "768_b256_full_straddling_skipped": (768, 768, 256, 256, 2, 2, 64,
                                         True, 128),
    "512_b256": (512, 512, 256, 256, 2, 2, 64, True, 128),
    "384_one_block_three_strips": (384, 384, 1024, 1024, 2, 2, 64, True,
                                   128),
    "96_no_subtile": (96, 96, 1024, 1024, 2, 2, 64, True, 0),
    "seq_k_longer": (256, 512, 256, 256, 2, 2, 64, True, 128),
    "seq_q_longer": (512, 256, 256, 256, 2, 2, 64, True, 128),
    "unequal_blocks_256x128": (512, 512, 256, 128, 2, 2, 64, True, 0),
    "gqa_2to1": (512, 512, 256, 256, 4, 2, 64, True, 128),
    "gqa_4to1": (512, 512, 256, 256, 4, 1, 64, True, 128),
    "not_causal": (512, 512, 256, 256, 2, 2, 64, False, 128),
}


class TestFlashAttentionSubTiles:
    """Causal skipping at sub-tile grain inside a block on the diagonal:
    out, dq, dk, dv against the fp32 reference at shapes that reach each
    path, and the plan that says which path a shape takes."""

    @pytest.mark.parametrize("case", list(_SUBTILE_CASES))
    def test_out_and_grads_match_reference(self, case):
        (seq_q, seq_k, block_q, block_k, heads, kv_heads, dim, causal,
         grain) = _SUBTILE_CASES[case]
        assert causal_plan(seq_q, seq_k, block_q, block_k).grain == grain
        q, _, _ = _qkv(heads=heads, seq=seq_q, dim=dim)
        _, k, v = _qkv(heads=heads, kv_heads=kv_heads, seq=seq_k, dim=dim,
                       seed=1)

        def flash(q, k, v):
            out = flash_attention(q, k, v, causal, None, block_q, block_k)
            return jnp.sum(out ** 2), out

        def ref(q, k, v):
            out = reference_attention(q, k, v, causal)
            return jnp.sum(out ** 2), out

        g_flash, out = jax.grad(flash, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
        g_ref, out_ref = jax.grad(ref, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
        np.testing.assert_allclose(out, out_ref, atol=2e-5, rtol=2e-5)
        for name, a, b in zip(("dq", "dk", "dv"), g_flash, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                       err_msg=name)

    @pytest.mark.parametrize("shape,grain,share", [
        # the cells: 1 whole block + 2 on the diagonal at 36/64, of 4
        ((2048, 2048, 1024, 1024), 128, 2.125 / 4),
        # sub-tiling off: 3 whole blocks of 4
        ((128, 128, 64, 64), 0, 3 / 4),
        ((512, 512, 256, 128), 0, 6 / 8),
        # one block, whole: nothing to skip without a grain
        ((96, 96, 1024, 1024), 0, 1.0),
        # one block of three strips: 6 of 9 sub-tiles
        ((384, 384, 1024, 1024), 128, 6 / 9),
        # 3 x 3 blocks of 256: 3 whole, 3 on the diagonal at 3/4, 3 skipped
        ((768, 768, 256, 256), 128, (3 + 3 * 0.75) / 9),
        # trailing kv blocks have no q block at all
        ((256, 512, 256, 256), 128, 0.75 / 2),
        # seq 8k: 28 whole blocks + 8 on the diagonal, of 64
        ((8192, 8192, 1024, 1024), 128, (28 + 8 * 36 / 64) / 64),
    ])
    def test_causal_plan(self, shape, grain, share):
        plan = causal_plan(*shape)
        assert plan.grain == grain
        assert plan.computed_share == pytest.approx(share, rel=1e-12)
        assert (plan.block_q, plan.block_k) == (
            fit_block(shape[0], shape[2]), fit_block(shape[1], shape[3]))


# The lowered text of the stack below with the finished kernels (measured
# here, 65,894 characters) plus 25 %: a kernel body that grows, or a kernel
# lowered once per layer again (4 layers: 12 custom calls, about 4x the
# text), grows what every launch traces, lowers and compiles.
_STACK_TEXT_BOUND = 82_000


def test_flash_stack_lowers_each_kernel_once(monkeypatch):
    """Set-up guard: a 4-layer stack of flash_attention under jax.grad, at
    the benchmark cells' attention shape, lowered for the TPU from here
    (no chip, no TPU compiler: lowering only). JAX lowers a jitted function
    once per signature and calls it, so the module holds 3 flash custom
    calls, not 3 per layer."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((2, 16, 2048, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8, 2048, 128), jnp.bfloat16)

    def loss(q, k, v):
        x = q
        for _ in range(4):
            x = flash_attention(x, k, v, True) + x
        return jnp.sum(x.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        q, kv, kv).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in (KERNEL_FWD, KERNEL_DQ, KERNEL_DKV):
        assert kernel in text, kernel
    assert len(text) < _STACK_TEXT_BOUND, len(text)


class TestFusedRmsNorm:
    def test_forward(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 256))
        w = jax.random.normal(jax.random.PRNGKey(1), (256,)) + 1.0
        np.testing.assert_allclose(
            fused_rms_norm(x, w), reference_rms_norm(x, w),
            atol=1e-5, rtol=1e-5,
        )

    def test_backward(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
        w = jax.random.normal(jax.random.PRNGKey(1), (256,)) + 1.0

        def loss_fused(x, w):
            return jnp.sum(fused_rms_norm(x, w) ** 2)

        def loss_ref(x, w):
            return jnp.sum(reference_rms_norm(x, w) ** 2)

        gx_f, gw_f = jax.grad(loss_fused, argnums=(0, 1))(x, w)
        gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(gx_f, gx_r, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gw_f, gw_r, atol=1e-4, rtol=1e-4)

    def test_under_jit_and_grad_composition(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        w = jnp.ones((128,))
        f = jax.jit(lambda x: fused_rms_norm(x, w).sum())
        assert np.isfinite(float(f(x)))
        assert np.isfinite(float(jax.jit(jax.grad(f))(x).sum()))


class TestMeshRmsNorm:
    """The fused norm inside a full-mesh shard_map (the only way a Mosaic
    kernel lowers under a multi-chip mesh): the kernel's backward sums dw
    over the rows of ITS call, so each shard holds a partial weight
    gradient and the shard_map transpose must psum it over the mesh. A
    missing psum crashes nothing — only these gradients show it."""

    @pytest.mark.parametrize("spec,shape", [
        # rows split four ways, nothing replicated
        (dict(data=2, fsdp=2), (8, 16, 128)),
        # rows split over fsdp AND sequence
        (dict(fsdp=2, sequence=2), (4, 16, 128)),
        # tensor replicates the rows: its shards compute the same block
        # twice, and dw must still come out once, not doubled
        (dict(fsdp=2, tensor=2), (4, 16, 128)),
        # a batch the dp axes do not divide stays whole on every device
        (dict(data=4), (3, 16, 128)),
        # 2-D input: rows only
        (dict(fsdp=4), (32, 128)),
    ])
    def test_grads_match_reference(self, cpu_devices, spec, shape):
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh, use_mesh

        mesh = create_mesh(MeshSpec(**spec), cpu_devices[:4])
        x = jax.random.normal(jax.random.PRNGKey(0), shape)
        w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:]) + 1.0
        # a cotangent that differs per row, so a shard's partial dw is
        # visibly not the total
        c = jax.random.normal(jax.random.PRNGKey(2), shape)

        def loss_mesh(x, w):
            with use_mesh(mesh):
                return jnp.sum(mesh_rms_norm(x, w) * c)

        def loss_ref(x, w):
            return jnp.sum(reference_rms_norm(x, w) * c)

        with use_mesh(mesh):
            out = jax.jit(mesh_rms_norm)(x, w)
        np.testing.assert_allclose(out, reference_rms_norm(x, w),
                                   atol=1e-5, rtol=1e-5)
        gx, gw = jax.jit(jax.grad(loss_mesh, argnums=(0, 1)))(x, w)
        gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(gx, gx_r, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gw, gw_r, atol=1e-4, rtol=1e-4)

    def test_plain_kernel_off_mesh_and_in_manual_region(self, cpu_devices):
        """No ambient mesh → the plain kernel; inside an already-manual
        region (pipeline stages, the manual grad-reduce axis) a nested
        full-mesh shard_map cannot be traced → the plain kernel on the
        caller's per-shard block."""
        from jax.sharding import PartitionSpec as P

        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh, use_mesh

        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        w = jnp.ones((128,)) * 1.5
        np.testing.assert_allclose(mesh_rms_norm(x, w),
                                   reference_rms_norm(x, w),
                                   atol=1e-5, rtol=1e-5)
        mesh = create_mesh(MeshSpec(data=2, fsdp=2), cpu_devices[:4])

        def body(xs, ws):
            return mesh_rms_norm(xs, ws)

        with use_mesh(mesh):
            out = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(P("data"), P()),
                out_specs=P("data"), axis_names=frozenset({"data"}),
                check_vma=False))(x, w)
        np.testing.assert_allclose(out, reference_rms_norm(x, w),
                                   atol=1e-5, rtol=1e-5)


class TestMeshFlashAttention:
    def test_sharded_matches_plain(self, cpu_devices):
        """mesh_flash_attention under a (data, fsdp, tensor) mesh: each
        device runs the kernel on its local batch/head block; values and
        grads match the unsharded kernel (a Pallas call is a custom call
        the SPMD partitioner cannot split on real TPU, so the shard_map
        wrapper is the multi-chip product path)."""
        import numpy as np
        from dlrover_tpu.ops.flash_attention import (
            flash_attention,
            mesh_flash_attention,
        )
        from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh, use_mesh

        mesh = create_mesh(MeshSpec(data=2, fsdp=2, tensor=2),
                           cpu_devices[:8])
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (4, 4, 64, 16), jnp.float32)
        k = jax.random.normal(kk, (4, 2, 64, 16), jnp.float32)  # GQA
        v = jax.random.normal(kv, (4, 2, 64, 16), jnp.float32)

        plain = flash_attention(q, k, v, True)

        def sharded_sum(q, k, v):
            with use_mesh(mesh):
                return jnp.sum(mesh_flash_attention(q, k, v, True) ** 2)

        def plain_sum(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True) ** 2)

        with use_mesh(mesh):
            sharded = jax.jit(mesh_flash_attention,
                              static_argnums=(3,))(q, k, v, True)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(plain),
                                   atol=1e-5, rtol=1e-5)
        g_sharded = jax.jit(jax.grad(sharded_sum, argnums=(0, 1, 2)))(
            q, k, v)
        g_plain = jax.grad(plain_sum, argnums=(0, 1, 2))(q, k, v)
        for gs, gp in zip(g_sharded, g_plain):
            np.testing.assert_allclose(np.asarray(gs), np.asarray(gp),
                                       atol=1e-4, rtol=1e-4)

    def test_no_mesh_falls_back(self):
        """Outside any mesh context the wrapper is the plain kernel."""
        import numpy as np
        from dlrover_tpu.ops.flash_attention import (
            flash_attention,
            mesh_flash_attention,
        )

        q = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 32, 8))
        out = mesh_flash_attention(q, q, q, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(flash_attention(q, q, q, True)),
            atol=1e-6)
