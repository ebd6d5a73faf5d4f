"""ops/paged_attention.py kernel tests (ISSUE 12).

The pallas block-gather kernel runs in interpret mode on CPU (the same
shrink-don't-mock stance as the flash/gmm kernels), verified against
the dense gather + masked-einsum reference it must agree with: GQA
grouping, sliding windows (whole skipped pages AND partially-masked
ones), int8-KV dequant scales, ragged final pages, and trash-page
table entries past the live length.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tensorflowonspark_tpu.ops.attention import dot_attention  # noqa: E402
from tensorflowonspark_tpu.ops.paged_attention import (  # noqa: E402
    TileLegalityError,
    bank_attention,
    bank_block,
    gather_pool,
    paged_attention,
    paged_gather_attention,
)


def _pools(rng, p=12, t=4, hkv=2, d=8, dtype=np.float32):
    k = jnp.asarray(rng.randn(p, t, hkv, d).astype(dtype))
    v = jnp.asarray(rng.randn(p, t, hkv, d).astype(dtype))
    return k, v


def _reference(q, kp, vp, tables, lengths, window=0, ks=None, vs=None):
    """Dense reference: gather + per-row causal/window mask (one query
    at position lengths-1)."""
    return paged_gather_attention(
        q[:, None], kp, vp, tables, (lengths - 1)[:, None],
        window=window, k_scale_pool=ks, v_scale_pool=vs,
    )[:, 0]


class TestKernel:
    def _case(self, b=3, h=4, hkv=2, d=8, p=12, t=4, nb=5, seed=0):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, h, d).astype(np.float32))
        kp, vp = _pools(rng, p, t, hkv, d)
        tables = jnp.asarray(rng.randint(1, p, (b, nb)), jnp.int32)
        lengths = jnp.asarray(
            rng.randint(1, nb * t + 1, (b,)), jnp.int32
        )
        return q, kp, vp, tables, lengths

    def test_matches_reference_full_causal(self):
        q, kp, vp, tables, lengths = self._case()
        out = paged_attention(q, kp, vp, tables, lengths)
        ref = _reference(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_gqa_grouping(self):
        # 6 query heads over 2 kv heads: the kernel's grouped reshape
        # must match dot_attention's grouping exactly
        q, kp, vp, tables, lengths = self._case(h=6, hkv=2)
        out = paged_attention(q, kp, vp, tables, lengths)
        ref = _reference(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_mha_single_group(self):
        q, kp, vp, tables, lengths = self._case(h=2, hkv=2)
        out = paged_attention(q, kp, vp, tables, lengths)
        ref = _reference(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    @pytest.mark.parametrize("window", [3, 4, 7, 100])
    def test_sliding_window(self, window):
        # windows that skip whole pages, split a page, and exceed the
        # sequence (equivalent to full causal)
        q, kp, vp, tables, lengths = self._case()
        out = paged_attention(q, kp, vp, tables, lengths, window=window)
        ref = _reference(q, kp, vp, tables, lengths, window=window)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_ragged_final_page_masked(self):
        # lengths that end mid-page: positions past length must not
        # contribute — poison them with huge values and check
        q, kp, vp, tables, lengths = self._case()
        lengths = jnp.asarray([1, 5, 18], jnp.int32)  # mid-page ends
        out = paged_attention(q, kp, vp, tables, lengths)
        # poison every pool position, then restore only the VISIBLE
        # ones through the tables — the output must not move
        poisoned_k = np.array(np.asarray(kp)) + 1e6
        poisoned_v = np.array(np.asarray(vp)) + 1e6
        for b in range(3):
            n = int(lengths[b])
            for pos in range(n):
                pg = int(tables[b, pos // 4])
                poisoned_k[pg, pos % 4] = np.asarray(kp)[pg, pos % 4]
                poisoned_v[pg, pos % 4] = np.asarray(vp)[pg, pos % 4]
        out2 = paged_attention(
            q, jnp.asarray(poisoned_k), jnp.asarray(poisoned_v),
            tables, lengths,
        )
        np.testing.assert_allclose(out, out2, atol=1e-4)

    def test_int8_kv_scales(self):
        rng = np.random.RandomState(1)
        q, kp, vp, tables, lengths = self._case(seed=1)
        sk = jnp.asarray(
            0.01 + 0.05 * rng.rand(*kp.shape[:3], 1).astype(np.float32)
        )
        sv = jnp.asarray(
            0.01 + 0.05 * rng.rand(*vp.shape[:3], 1).astype(np.float32)
        )
        kq = jnp.clip(jnp.round(kp / sk), -127, 127).astype(jnp.int8)
        vq = jnp.clip(jnp.round(vp / sv), -127, 127).astype(jnp.int8)
        out = paged_attention(
            q, kq, vq, tables, lengths, k_scale_pool=sk, v_scale_pool=sv,
        )
        ref = _reference(
            q, kq, vq, tables, lengths, ks=sk, vs=sv,
        )
        np.testing.assert_allclose(out, ref, atol=1e-5)
        # and the dequantized pools agree with running float attention
        # on the same (quantized) content
        kf = kq.astype(jnp.float32) * sk
        vf = vq.astype(jnp.float32) * sv
        reff = _reference(q, kf, vf, tables, lengths)
        np.testing.assert_allclose(out, reff, atol=1e-3)

    def test_shared_page_two_slots(self):
        # the point of the layout: two tables referencing the SAME
        # physical page read the same bytes — outputs for identical
        # histories are identical
        rng = np.random.RandomState(2)
        kp, vp = _pools(rng)
        q1 = rng.randn(1, 4, 8).astype(np.float32)
        q = jnp.asarray(np.concatenate([q1, q1]))
        tables = jnp.asarray([[3, 5, 7], [3, 5, 9]], jnp.int32)
        lengths = jnp.asarray([7, 7], jnp.int32)  # inside shared pages
        out = paged_attention(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(out[0], out[1], atol=1e-6)

    def test_window_page_skip_equals_mask(self):
        # a long table where the window leaves only the last page
        # relevant: skipped pages must equal explicitly-masked ones
        q, kp, vp, tables, lengths = self._case(nb=8)
        lengths = jnp.asarray([30, 31, 32], jnp.int32)
        out = paged_attention(q, kp, vp, tables, lengths, window=3)
        ref = _reference(q, kp, vp, tables, lengths, window=3)
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestGatherFallback:
    def test_matches_contiguous_dot_attention(self):
        # gather through the table then mask == dot_attention over the
        # SAME contiguous banks (what the multi-token prefill/verify
        # paths rely on for bit-identity with the contiguous layout)
        rng = np.random.RandomState(3)
        kp, vp = _pools(rng)
        b, s, h, d = 2, 3, 4, 8
        q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
        tables = jnp.asarray(rng.randint(1, 12, (b, 4)), jnp.int32)
        positions = jnp.asarray([[4, 5, 6], [9, 10, 11]], jnp.int32)
        out = paged_gather_attention(
            q, kp, vp, tables, positions, span=14, window=5,
        )
        k = gather_pool(kp, tables, span=14)
        v = gather_pool(vp, tables, span=14)
        kpos = jnp.arange(14)
        vis = kpos[None, None, :] <= positions[:, :, None]
        vis = jnp.logical_and(
            vis, kpos[None, None, :] > positions[:, :, None] - 5
        )
        mask = jnp.where(vis, 0.0, -jnp.inf)[:, None]
        ref = dot_attention(q, k, v, causal=False, mask=mask)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_span_slices_gathered_banks(self):
        rng = np.random.RandomState(4)
        kp, _ = _pools(rng)
        tables = jnp.asarray([[1, 2, 3]], jnp.int32)
        g = gather_pool(kp, tables, span=10)
        assert g.shape == (1, 10, 2, 8)
        np.testing.assert_array_equal(
            np.asarray(g[0, :4]), np.asarray(kp[1])
        )
        np.testing.assert_array_equal(
            np.asarray(g[0, 8:10]), np.asarray(kp[3][:2])
        )

    def test_errors(self):
        rng = np.random.RandomState(5)
        kp, vp = _pools(rng)
        q = jnp.zeros((1, 3, 8), jnp.float32)  # 3 heads over 2 kv
        tables = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="multiple of kv heads"):
            paged_attention(q, kp, vp, tables, jnp.ones((1,), jnp.int32))
        q = jnp.zeros((1, 4, 8), jnp.float32)
        with pytest.raises(ValueError, match="v_scale_pool"):
            paged_attention(
                q, kp, vp, tables, jnp.ones((1,), jnp.int32),
                k_scale_pool=jnp.ones((12, 4, 2, 1), jnp.float32),
            )


def _slot_reference(q, k, v, positions, pad_start, window=0, ks=None,
                    vs=None):
    """The per-slot mask the model builds for contiguous banks (causal,
    window, pad region, self always visible) under ``dot_attention``."""
    kpos = jnp.arange(k.shape[1])
    qpos = positions[:, None]
    vis = kpos[None, :] <= qpos
    if window:
        vis = jnp.logical_and(vis, kpos[None, :] > qpos - window)
    vis = jnp.logical_or(
        jnp.logical_and(vis, kpos[None, :] >= pad_start[:, None]),
        kpos[None, :] == qpos,
    )
    mask = jnp.where(vis, 0.0, -jnp.inf)[:, None, None, :]
    return dot_attention(
        q[:, None], k, v, causal=False, mask=mask, k_scale=ks, v_scale=vs,
    )[:, 0]


#: bank cases at a tile-legal geometry (head_dim 128, a bank of three
#: 128-token blocks): (heads, kv heads, positions, pad_start, window,
#: int8)
_BANK_CASES = {
    "gqa4_pad0": (4, 1, [5, 200, 383], [0, 0, 0], 0, False),
    "mha": (2, 2, [0, 130, 300], [0, 0, 0], 0, False),
    "pad_in_first_block": (4, 1, [40, 200, 383], [5, 127, 1], 0, False),
    "pad_past_first_block": (4, 1, [140, 300, 383], [128, 260, 129], 0,
                             False),
    "block_edges": (4, 2, [127, 128, 255, 256], [0, 0, 128, 256], 0,
                    False),
    "window_shorter_than_span": (4, 1, [300, 383, 90], [0, 10, 0], 100,
                                 False),
    "window_and_pad": (4, 2, [383, 260], [250, 100], 140, False),
    "int8_scales": (4, 2, [33, 200, 383], [0, 130, 7], 0, True),
    "int8_window": (4, 1, [300, 383], [20, 0], 100, True),
    # an idle lane's pad_start lies past its position: self only
    "idle_lane": (4, 1, [0, 77, 200], [384, 384, 0], 0, False),
}


class TestBanks:
    """``bank_attention``: the same kernel body over contiguous banks,
    against the model's masked ``dot_attention``."""

    S, D = 384, 128

    def _banks(self, rng, b, hkv, int8):
        shape = (b, self.S, hkv, self.D)
        k = jnp.asarray(rng.randn(*shape).astype(np.float32))
        v = jnp.asarray(rng.randn(*shape).astype(np.float32))
        if not int8:
            return k, v, None, None
        sk = jnp.asarray(
            0.01 + 0.05 * rng.rand(*shape[:3], 1).astype(np.float32))
        sv = jnp.asarray(
            0.01 + 0.05 * rng.rand(*shape[:3], 1).astype(np.float32))
        kq = jnp.clip(jnp.round(k / sk), -127, 127).astype(jnp.int8)
        vq = jnp.clip(jnp.round(v / sv), -127, 127).astype(jnp.int8)
        return kq, vq, sk, sv

    @pytest.mark.parametrize("case", sorted(_BANK_CASES))
    def test_matches_slot_mask_reference(self, case):
        h, hkv, positions, pad_start, window, int8 = _BANK_CASES[case]
        rng = np.random.RandomState(len(case))
        b = len(positions)
        q = jnp.asarray(rng.randn(b, h, self.D).astype(np.float32))
        k, v, sk, sv = self._banks(rng, b, hkv, int8)
        positions = jnp.asarray(positions, jnp.int32)
        pad_start = jnp.asarray(pad_start, jnp.int32)
        assert bank_block(self.S, self.D, k.dtype) == 128
        out = bank_attention(
            q, k, v, positions, pad_start, window=window,
            k_scale=sk, v_scale=sv,
        )
        ref = _slot_reference(
            q, k, v, positions, pad_start, window=window, ks=sk, vs=sv,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_dead_positions_never_reach_the_result(self):
        # NaN in every key outside [pad_start, position] and in every
        # value of a block the span does not touch: a dead block that
        # was fetched or computed, or a masked logit that was not
        # replaced, would turn the output NaN.  (A masked position's
        # VALUE inside a live block meets a zero probability, as under
        # dot_attention; it is poisoned with a huge finite number.)
        rng = np.random.RandomState(7)
        positions = jnp.asarray([130, 383, 60, 255], jnp.int32)
        pad_start = jnp.asarray([129, 140, 0, 384], jnp.int32)
        b, h, hkv, t = 4, 4, 2, 128
        q = jnp.asarray(rng.randn(b, h, self.D).astype(np.float32))
        k, v, _, _ = self._banks(rng, b, hkv, False)
        out = bank_attention(q, k, v, positions, pad_start)
        kp, vp = np.array(k), np.array(v)
        for r in range(b):
            hi = int(positions[r])
            lo = min(int(pad_start[r]), hi)
            dead = np.ones((self.S,), bool)
            dead[lo:hi + 1] = False
            kp[r, dead] = np.nan
            vp[r, dead] = 1e30
            dead_block = dead.copy()
            dead_block[(lo // t) * t:(hi // t + 1) * t] = False
            vp[r, dead_block] = np.nan
        out2 = bank_attention(
            q, jnp.asarray(kp), jnp.asarray(vp), positions, pad_start,
        )
        assert np.isfinite(np.asarray(out2)).all()
        np.testing.assert_allclose(out, out2, atol=1e-6)

    def test_bank_block_reads_the_geometry(self):
        assert bank_block(1536, 128, jnp.bfloat16) == 256
        assert bank_block(512, 128, jnp.bfloat16) == 256
        assert bank_block(384, 256, jnp.int8) == 128
        # a head the lane does not tile, a bank no block divides
        assert bank_block(1536, 64, jnp.bfloat16) is None
        assert bank_block(1541, 128, jnp.bfloat16) is None
        with pytest.raises(TileLegalityError):
            bank_attention(
                jnp.zeros((1, 2, 8)), jnp.zeros((1, 16, 2, 8)),
                jnp.zeros((1, 16, 2, 8)), jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.int32),
            )

    def test_paged_starts_skip_and_mask(self):
        # the paged entry point takes the same per-slot first visible
        # position: blocks wholly below it are skipped, the rest masked
        rng = np.random.RandomState(3)
        b, h, hkv, d, p, t, nb = 3, 4, 2, 8, 12, 4, 5
        q = jnp.asarray(rng.randn(b, h, d).astype(np.float32))
        kp, vp = _pools(rng, p, t, hkv, d)
        tables = jnp.asarray(rng.randint(1, p, (b, nb)), jnp.int32)
        lengths = jnp.asarray([20, 9, 14], jnp.int32)
        starts = jnp.asarray([7, 8, 0], jnp.int32)
        out = paged_attention(q, kp, vp, tables, lengths, starts=starts)
        k = gather_pool(kp, tables)
        v = gather_pool(vp, tables)
        ref = _slot_reference(q, k, v, lengths - 1, starts)
        np.testing.assert_allclose(out, ref, atol=1e-5)
