"""Kernel correctness tests: Pallas flash attention (interpret mode on the
CPU mesh), ring attention vs. the dense oracle, fused layers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_common
from ray_tpu.ops import (apply_rope, cross_entropy_loss, flash_attention,
                         layernorm, mha_reference, ring_attention, rmsnorm,
                         rope_cache)
from ray_tpu.parallel import MeshSpec, virtual_mesh


def _qkv(key, b=2, s=128, h=4, d=32, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (jax.random.normal(k1, shape, dtype),
            jax.random.normal(k2, shape, dtype),
            jax.random.normal(k3, shape, dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_uneven_blocks(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), s=192)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grad_flows(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), s=64)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, block_q=32, block_k=32).sum()

        def loss_ref(q, k, v):
            return mha_reference(q, k, v).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_bf16(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        ref = mha_reference(q, k, v)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref, dtype=np.float32),
                                   atol=3e-2, rtol=3e-2)


def _path_counts():
    from ray_tpu.ops.flash_attention import PATH_COUNTS

    return dict(PATH_COUNTS)


def _took(before, layout, calls=1):
    """flash_attention was traced ``calls`` times since ``before``, every
    time by ``layout``."""
    after = _path_counts()
    delta = {k: after[k] - before.get(k, 0) for k in after}
    assert delta.get(layout, 0) == calls and sum(delta.values()) == calls, \
        (layout, delta)


class TestFlashAttentionLayouts:
    """The kernels on the model's own [B, S, H*D] arrays (heads of 64 or
    128, merged width a multiple of 128) and on [B*H, S, D] copies (every
    other head size), against the dense oracle; interpret mode."""

    # block 128 at S=256 streams K/V: flash_fwd, flash_bwd_dq, flash_bwd_dkv
    CASES = [(h, d, s, causal, 1024)
             for h, d in [(2, 64), (4, 64), (1, 128), (2, 128)]
             for s in (128, 256) for causal in (True, False)]
    CASES += [(4, 64, 256, True, 128), (2, 128, 256, False, 128)]

    @pytest.mark.parametrize("h,d,s,causal,block", CASES)
    def test_merged_matches_reference(self, h, d, s, causal, block):
        q, k, v = _qkv(jax.random.PRNGKey(h * d + s), s=s, h=h, d=d)
        w = jax.random.normal(jax.random.PRNGKey(9), q.shape)

        def loss(attn):
            return lambda q, k, v: (attn(q, k, v) * w).sum()

        flash = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=causal, block_q=block, block_k=block)
        ref = lambda q, k, v: mha_reference(q, k, v, causal=causal)  # noqa: E731
        before = _path_counts()
        out = flash(q, k, v)
        g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        _took(before, "merged", calls=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("h,d,s,layout", [
        (2, 32, 128, "relayout"), (4, 32, 128, "relayout"),
        (3, 64, 128, "relayout"),   # 192 lanes: no whole 128-lane blocks
        (2, 64, 64, "reference"),   # S no multiple of 128: no kernel
    ])
    def test_other_shapes_keep_the_old_route(self, h, d, s, layout):
        q, k, v = _qkv(jax.random.PRNGKey(h + d), s=s, h=h, d=d)
        before = _path_counts()
        out = flash_attention(q, k, v, causal=True)
        g1 = jax.grad(lambda *a: flash_attention(*a).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        _took(before, layout, calls=2)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(mha_reference(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        g2 = jax.grad(lambda *a: mha_reference(*a).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("h,d,moved", [(4, 64, False), (4, 32, True)])
    def test_no_transpose_around_the_merged_kernels(self, h, d, moved):
        """Forward and backward of the merged layout hold no transpose
        outside the kernels; the old route (heads of 32) does."""
        q, k, v = _qkv(jax.random.PRNGKey(4), s=128, h=h, d=d)
        before = _path_counts()
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: flash_attention(*a).sum(), argnums=(0, 1, 2)))(q, k, v)
        _took(before, "relayout" if moved else "merged")

        def prims(jp):
            for eqn in jp.eqns:
                yield eqn.primitive.name
                if eqn.primitive.name == "pallas_call":
                    continue    # what a kernel does inside is its own
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from prims(sub)

        names = list(prims(jaxpr.jaxpr))
        assert names.count("pallas_call") == 2
        assert ("transpose" in names) == moved

    def test_path_is_a_flight_recorder_event(self):
        from ray_tpu.ops.flash_attention import BAND_COUNTS
        from ray_tpu.perf.recorder import get_recorder

        rec = get_recorder()
        was, rec.enabled = rec.enabled, True
        before = dict(BAND_COUNTS)
        try:
            q, k, v = _qkv(jax.random.PRNGKey(5), s=256, h=2, d=64)
            flash_attention(q, k, v)
            flash_attention(q, k, v, causal=False)
            flash_attention(q[:, :64], k[:, :64], v[:, :64])
            evs = [e for e in rec.snapshot()
                   if e["kind"] == "rtpu.ops.flash.path"][-3:]
        finally:
            rec.enabled = was
        assert evs[0]["label"] == "merged"
        assert evs[0]["data"] == {"layout": "merged", "heads_per_block": 2,
                                  "hd": 64, "S": 256, "bands": 2}
        assert [e["data"]["bands"] for e in evs] == [2, 1, 0]
        assert evs[2]["label"] == "reference"
        # one count per traced call, keyed by its number of bands
        assert {n: BAND_COUNTS[n] - before.get(n, 0)
                for n in (0, 1, 2)} == {0: 1, 1: 1, 2: 1}


def _band_counts():
    from ray_tpu.ops.flash_attention import BAND_COUNTS

    return dict(BAND_COUNTS)


def _kernels_of(jp):
    """The ``name`` of every pallas_call of a jaxpr, inner jaxprs too."""
    for eqn in jp.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernels_of(sub)


def _grads(attn, w, q, k, v):
    return jax.grad(lambda q, k, v: (attn(q, k, v) * w).sum(),
                    argnums=(0, 1, 2))(q, k, v)


class TestFlashAttentionCausalBands:
    """A causal call that one program holds whole computes row bands of a
    quarter of the sequence, each on the columns it can see, and not the
    masked half of the square; every other call runs the unbanded code.
    Interpret mode, against the dense oracle."""

    @pytest.mark.parametrize("s", [256, 512, 1024])
    @pytest.mark.parametrize("h,d,layout", [
        (4, 64, "merged"), (2, 128, "merged"), (2, 32, "relayout")])
    def test_banded_matches_reference(self, h, d, layout, s):
        q, k, v = _qkv(jax.random.PRNGKey(h * d + s), b=1, s=s, h=h, d=d)
        w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        paths, bands = _path_counts(), _band_counts()
        out = flash_attention(q, k, v, causal=True)
        g1 = _grads(flash_attention, w, q, k, v)
        _took(paths, layout, calls=2)
        want = s // max(128, s // 4)
        after = _band_counts()
        assert want > 1 and after[want] - bands.get(want, 0) == 2
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(mha_reference(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(g1, _grads(mha_reference, w, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("causal,block", [(False, 1024), (True, 512)])
    def test_other_calls_are_not_banded(self, causal, block):
        """Non-causal, and S=1024 streamed in blocks of 512 (whose kernels
        skip masked blocks by predicate): one band, the whole block."""
        q, k, v = _qkv(jax.random.PRNGKey(11), b=1, s=1024, h=2, d=64)
        w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        flash = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=causal, block_q=block, block_k=block)
        ref = lambda q, k, v: mha_reference(q, k, v, causal=causal)  # noqa: E731
        bands = _band_counts()
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v)
        after = _band_counts()
        assert {n: after[n] - bands.get(n, 0) for n in after
                if after[n] != bands.get(n, 0)} == {1: 1}
        kernels = ({"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} if causal
                   else {"flash_fwd_single", "flash_bwd_fused"})
        assert set(_kernels_of(jaxpr.jaxpr)) == kernels
        out = flash(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(_grads(flash, w, q, k, v), _grads(ref, w, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("s", range(128, 2049, 128))
    def test_band_geometry(self, s):
        """Every (row, col <= row) lies in exactly one band's
        [rows] x [0, end), and no band reaches a column past its own
        diagonal tile; heights are whole 128-lane tiles that divide S."""
        from ray_tpu.ops.flash_attention import _band_height, _row_bands

        band = _band_height(s, True, 2048, 2048)
        assert band % 128 == 0 and s % band == 0
        assert band == 128 or band <= s // 4
        bands = _row_bands(s, s, band)
        owner = np.zeros(s, np.int64)
        for r0, h, end in bands:
            assert h == band and end == r0 + h   # its own diagonal tile
            owner[r0:r0 + h] += 1
        assert (owner == 1).all()                # rows partitioned
        ends = np.repeat([end for _, _, end in bands], band)
        rows = np.arange(s)
        assert (ends > rows).all()               # every col <= row computed
        assert (ends - rows <= band).all()       # and nothing past the tile
        # what is not banded is one band, the whole block
        assert _band_height(s, False, 2048, 2048) == 0
        assert _band_height(s, True, s // 2 if s > 128 else 64, 2048) == 0
        assert _row_bands(s, s, 0) == [(0, s, s)]


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        from ray_tpu.jax_compat import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = virtual_mesh(8, MeshSpec(dp=1, sp=4, tp=2))
        q, k, v = _qkv(jax.random.PRNGKey(4), b=2, s=64, h=4, d=16)
        spec = P(("dp", "fsdp"), "sp", "tp", None)
        fn = shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                           causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        out = jax.jit(fn)(q, k, v)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grad_matches_dense(self):
        from ray_tpu.jax_compat import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = virtual_mesh(8, MeshSpec(dp=2, sp=4))
        q, k, v = _qkv(jax.random.PRNGKey(5), b=2, s=32, h=2, d=8)
        spec = P(("dp", "fsdp"), "sp", "tp", None)

        ring = shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

        g1 = jax.grad(lambda q, k, v: jax.jit(ring)(q, k, v).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: mha_reference(q, k, v).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)


class TestLayers:
    def test_rmsnorm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        w = jnp.ones((16,)) * 2.0
        y = rmsnorm(x, w)
        norm = np.asarray(x) / np.sqrt(
            np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(y), norm * 2.0, atol=1e-5)

    def test_layernorm_matches_numpy(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        w, b = jnp.ones((16,)), jnp.zeros((16,))
        y = layernorm(x, w, b)
        xn = np.asarray(x)
        ref = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
            xn.var(-1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5)

    def test_rope_rotation_preserves_norm(self):
        cos, sin = rope_cache(32, 8)
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 2, 8))
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                                   np.linalg.norm(np.asarray(x), axis=-1),
                                   atol=1e-5)

    def test_rope_positions(self):
        cos, sin = rope_cache(32, 8)
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 2, 8))
        pos = jnp.array([[4, 5, 6, 7]])
        y1 = apply_rope(x, cos, sin, positions=pos)
        full = jnp.concatenate([jnp.zeros((1, 4, 2, 8), x.dtype), x], axis=1)
        y2 = apply_rope(full, cos, sin)[:, 4:]
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)

    def test_rmsnorm_one_plus_w(self):
        """ISSUE 52: ``plus_one`` is the gain 1 + w (w from zero: no gain);
        the same body as the plain form handed 1 + w."""
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
        y = rmsnorm(x, w, plus_one=True)
        norm = np.asarray(x) / np.sqrt(
            np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(y),
                                   norm * (1.0 + np.asarray(w)), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(rmsnorm(x, 1.0 + w)))
        np.testing.assert_allclose(
            np.asarray(rmsnorm(x, jnp.zeros(16), plus_one=True)), norm,
            atol=1e-6)

    @pytest.mark.parametrize("name", ["silu", "sigmoid"])
    def test_the_norm_then_the_gate(self, name):
        """ISSUE 52: rmsnorm(y) * w * act(gate), the norm FIRST (Gated
        DeltaNet under SiLU, KDA under the sigmoid: one body), by hand;
        ``gated_rmsnorm`` gates before the norm and differs."""
        from ray_tpu.ops import (gated_rmsnorm, rmsnorm_then_gate,
                                 sigmoid_gated_rmsnorm)

        y = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16))
        gate = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 16))
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
        yn, gn = np.asarray(y, np.float64), np.asarray(gate, np.float64)
        sig = 1.0 / (1.0 + np.exp(-gn))
        want = yn / np.sqrt(np.mean(yn * yn, -1, keepdims=True) + 1e-6) \
            * np.asarray(w) * (gn * sig if name == "silu" else sig)
        got = rmsnorm_then_gate(y, gate, w, activation=getattr(jax.nn, name))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
        if name == "sigmoid":
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(sigmoid_gated_rmsnorm(y, gate, w)))
        else:
            assert float(jnp.abs(got - gated_rmsnorm(y, gate, w)).max()) > 0.1

    def test_rope_on_the_first_channels_of_a_head(self):
        """ISSUE 52: tables half as wide as a quarter of the head turn its
        first 16 channels of 64, channel i with i + 8, by position x
        base^(-i/8), and leave the other 48 as they are."""
        cos, sin = rope_cache(32, 16, 1e4)
        assert cos.shape == (32, 8)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 3, 64))
        y = np.asarray(apply_rope(x, cos, sin))
        xn = np.asarray(x, np.float64)
        ang = np.arange(32)[:, None] * 1e4 ** (-np.arange(8) / 8.0)[None]
        c, s_ = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        lo, hi = xn[..., :8], xn[..., 8:16]
        np.testing.assert_allclose(y[..., :8], lo * c - hi * s_, atol=1e-5)
        np.testing.assert_allclose(y[..., 8:16], lo * s_ + hi * c, atol=1e-5)
        np.testing.assert_array_equal(y[..., 16:], np.asarray(x)[..., 16:])
        # the whole head: what the call was before
        full = rope_cache(32, 64, 1e4)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(apply_rope(x, *full)), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1), atol=1e-5)

    def test_cross_entropy(self):
        logits = jnp.array([[[2.0, 0.0, 0.0], [0.0, 3.0, 0.0]]])
        labels = jnp.array([[0, -100]])
        loss = cross_entropy_loss(logits, labels)
        expected = -np.log(np.exp(2.0) / (np.exp(2.0) + 2.0))
        np.testing.assert_allclose(float(loss), expected, rtol=1e-5)


class TestKernelCommon:
    """ISSUE 61: what the kernel files share (``ops/kernel_common.py``)."""

    @pytest.mark.parametrize("block,seq,want", [
        (1024, 1024, 1024),     # the block divides
        (1024, 768, 768),       # a sequence shorter than the block
        (512, 768, 384),        # the largest 128-multiple that divides
        (1024, 8320, 640),      # 65 tiles: 5 of them
        (256, 128, 128)])       # never under a tile
    def test_fit_block_is_the_largest_tile_multiple_that_divides(
            self, block, seq, want):
        got = kernel_common.fit_block(block, seq)
        assert got == want
        assert seq % got == 0 and got % kernel_common.LANES == 0

    @pytest.mark.parametrize("tokens,added", [(150, 42), (128, 0)])
    def test_pad_tokens_appends_zeros_to_whole_chunks(self, tokens, added):
        """Every array along its SECOND axis, whatever its rank; a whole
        number of chunks comes back as it went in."""
        x = jnp.ones((2, tokens, 3, 4))
        beta = jnp.ones((2, tokens))
        (xp, bp), pad = kernel_common.pad_tokens((x, beta), 64)
        assert pad == added
        assert xp.shape == (2, tokens + added, 3, 4)
        assert bp.shape == (2, tokens + added)
        assert float(xp[:, tokens:].sum()) == 0.0
        assert float(bp[:, tokens:].sum()) == 0.0
        assert float(xp.sum()) == x.size and float(bp.sum()) == beta.size
        if not added:
            assert xp is x and bp is beta
