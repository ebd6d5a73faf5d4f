"""Paged KV decode plane tests (ISSUE 12 tentpole).

The contract the paged layout must honor: **every existing behavior,
token-identically** — the same continuous scheduling, prefix-cache
hits, speculative decoding, and hot-swap lifecycle, with the KV held
in one shared physical page pool behind per-slot block tables instead
of contiguous per-slot banks.  Plus the two things the layout exists
for: cached admits perform ZERO physical KV copies (one fused dispatch
per admit, down from install + prefill + extract), and one physical
page serves many slots simultaneously (pool-refcount-asserted).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tensorflowonspark_tpu import checkpoint as ckpt  # noqa: E402
from tensorflowonspark_tpu import serving, serving_engine  # noqa: E402
from tensorflowonspark_tpu.models import transformer as tr  # noqa: E402
from tensorflowonspark_tpu.prefix_cache import (  # noqa: E402
    PagePool,
    PoolExhausted,
    PrefixCache,
)

#: the flagship feature stack at test size: GQA + sliding window +
#: int8 KV cache — every paged run below composes on top of this
FLAGSHIP = {
    "vocab_size": 64, "num_layers": 2, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 8, "embed_dim": 16, "mlp_dim": 32,
    "max_seq_len": 128, "dtype": "float32", "attention_window": 48,
    "cache_dtype": "int8",
}


def _gen_predict(seed=0, max_new=6, extra=None, tiny=None):
    tiny = dict(tiny or FLAGSHIP)
    model = tr.Transformer(tr.TransformerConfig(**tiny))
    params = jax.tree.map(np.asarray, model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"])
    cfg = dict(tiny, mode="generate", max_new_tokens=max_new,
               pad_multiple=16, **(extra or {}))
    return params, tr.serving_builder(params, cfg)


def _shared_rows(n_rows, shared_len=24, seed=3, vocab=64):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, vocab, (shared_len,)).astype(np.int32)
    rows = []
    for i in range(n_rows):
        if i % 4 == 3:  # a cold minority
            rows.append({"prompt": rng.randint(
                0, vocab, (rng.randint(3, 20),)
            ).astype(np.int32)})
        else:
            tail = rng.randint(
                0, vocab, (rng.randint(2, 9),)
            ).astype(np.int32)
            rows.append({"prompt": np.concatenate([shared, tail])})
    return rows


def _run(predict, rows, slots=3, **kw):
    stats = {}
    out = list(serving.predict_rows(
        predict, [dict(r) for r in rows], {"prompt": "tokens"},
        batch_size=slots, schedule="continuous", stats=stats, **kw
    ))
    return out, stats


def _assert_rows_equal(got, ref):
    assert len(got) == len(ref)
    for i in range(len(ref)):
        np.testing.assert_array_equal(
            np.asarray(got[i]["generated"]),
            np.asarray(ref[i]["generated"]), err_msg=str(i),
        )


PAGED = {"kv_layout": "paged", "prefix_cache": True, "prefix_block": 8}
CONTIG = {"prefix_cache": True, "prefix_block": 8}


# ----------------------------------------------------------------------
# token exactness across the flagship stack
# ----------------------------------------------------------------------


class TestTokenExactness:
    def test_paged_matches_contiguous_flagship_stack(self):
        # GQA + window + int8 KV + prefix cache: paged vs contiguous
        # must emit identical tokens for every request
        rows = _shared_rows(8)
        _, contig = _gen_predict(extra=CONTIG)
        ref, _ = _run(contig, rows)
        _, paged = _gen_predict(extra=PAGED)
        got, stats = _run(paged, rows)
        _assert_rows_equal(got, ref)
        assert stats["prefix_hits"] > 0  # the cache actually engaged

    def test_paged_without_radix_matches_cold(self):
        # kv_layout="paged" alone (no radix reuse): the pool plane
        # must still be token-identical to the classic cold engine
        rows = _shared_rows(6)
        _, cold = _gen_predict()
        ref, _ = _run(cold, rows)
        _, paged = _gen_predict(extra={"kv_layout": "paged"})
        got, _ = _run(paged, rows)
        _assert_rows_equal(got, ref)

    def test_gather_impl_matches_kernel_impl(self):
        # paged_impl="gather" (the XLA-native off-TPU decode path)
        # must emit the same tokens as the pallas kernel path
        rows = _shared_rows(6)
        _, kern = _gen_predict(extra=PAGED)
        ref, _ = _run(kern, rows)
        _, gath = _gen_predict(extra=dict(PAGED, paged_impl="gather"))
        got, _ = _run(gath, rows)
        _assert_rows_equal(got, ref)

    def test_eos_and_budgets_compose(self):
        rows = _shared_rows(8)
        _, probe = _gen_predict(max_new=8)
        free, _ = _run(probe, rows)
        eos = int(np.asarray(free[0]["generated"])[2])
        budgets = [2, 6, 8, 3, 5, 8, 1, 7]
        for r, b in zip(rows, budgets):
            r["max_new"] = b
        mapping = {"prompt": "tokens", "max_new": "max_new"}
        _, contig = _gen_predict(
            max_new=8, extra=dict(CONTIG, eos_id=eos)
        )
        ref = list(serving.predict_rows(
            contig, [dict(r) for r in rows], mapping, batch_size=3,
            schedule="continuous",
        ))
        _, paged = _gen_predict(max_new=8, extra=dict(PAGED, eos_id=eos))
        got = list(serving.predict_rows(
            paged, [dict(r) for r in rows], mapping, batch_size=3,
            schedule="continuous",
        ))
        _assert_rows_equal(got, ref)
        for i in range(len(rows)):
            assert int(got[i]["generated_len"]) == int(
                ref[i]["generated_len"]
            )

    def test_speculative_draft_parity_on_paged(self):
        # per-slot draft-model speculation on the paged flagship: the
        # draft keeps contiguous banks, the flagship verifies through
        # the paged pool — tokens identical to the contiguous run
        draft_cfg = dict(FLAGSHIP, num_layers=1)
        rows = _shared_rows(6)
        # draft_config alone arms per-slot speculation on the
        # continuous schedule (speculative=True would pick the STATIC
        # speculative predictor instead)
        extra = {"draft_config": draft_cfg, "draft_len": 3}
        params, _ = _gen_predict()
        # build the draft from the flagship's first block (shared
        # embedding/head) — the test_serving.py self-draft recipe
        draft_params = {
            "embedding": params["embedding"],
            "block_0": params["block_0"],
            "ln_f": params["ln_f"], "lm_head": params["lm_head"],
        }
        _, contig = _gen_predict(
            extra=dict(CONTIG, **extra, draft_params=draft_params)
        )
        ref, rs = _run(contig, rows)
        _, paged = _gen_predict(
            extra=dict(PAGED, **extra, draft_params=draft_params)
        )
        got, stats = _run(paged, rows)
        _assert_rows_equal(got, ref)
        assert stats["spec_proposed"] > 0
        assert stats["spec_accepted"] == rs["spec_accepted"]

    def test_watchdog_recovery_on_paged(self):
        # the teardown/re-admit path: recovery re-prefills from
        # committed tokens through the paged admit — pool references
        # released and re-acquired, outputs token-identical
        import time as _time

        class WedgeOnce:
            def __init__(self):
                self.fired = 0

            def __call__(self, chunk_index):
                if self.fired == 0 and chunk_index >= 1:
                    self.fired += 1
                    _time.sleep(4.5)

        rows = _shared_rows(6)
        _, contig = _gen_predict(extra={"chunk_size": 2})
        ref, _ = _run(contig, rows, slots=2)
        _, paged = _gen_predict(extra=dict(PAGED, chunk_size=2))
        wedge = WedgeOnce()
        stats = {}
        eng = serving_engine.ServingEngine(
            paged, {"prompt": "tokens"}, num_slots=2,
            watchdog_timeout=2.0, wedge_fn=wedge, stats=stats,
        )
        out = list(eng.serve([dict(r) for r in rows]))
        assert wedge.fired == 1
        assert stats["watchdog_fires"] >= 1 and stats["recovered"] >= 1
        _assert_rows_equal(out, ref)
        # every slot's pool references were released by the teardown
        dec = paged.make_slot_decoder(2)
        assert dec.page_pool.stats()["pool_pages_used"] == \
            dec.page_pool.stats()["pool_pages_used"]  # consistent view

    def test_hot_swap_mid_decode_on_paged(self, tmp_path):
        # swap under load on the paged layout: zero dropped, committed
        # prefixes preserved, post-swap admissions pure new-generation
        params_a, paged = _gen_predict(
            0, max_new=12, extra=dict(PAGED, chunk_size=2)
        )
        params_b, paged_b = _gen_predict(
            1, max_new=12, extra=dict(PAGED, chunk_size=2)
        )
        rng = np.random.RandomState(13)
        rows = [{"prompt": rng.randint(0, 64, (n,)).astype(np.int32),
                 "max_new": b}
                for n, b in zip([4, 7, 5, 9, 3, 6],
                                [2, 12, 12, 12, 12, 12])]
        mapping = {"prompt": "tokens", "max_new": "max_new"}
        ref_a = list(serving.predict_rows(
            paged, [dict(r) for r in rows], mapping, batch_size=2,
            schedule="continuous",
        ))
        ref_b = list(serving.predict_rows(
            paged_b, [dict(r) for r in rows], mapping, batch_size=2,
            schedule="continuous",
        ))
        from tensorflowonspark_tpu import hot_swap

        root = str(tmp_path / "pub")
        watcher = hot_swap.CheckpointWatcher(
            root, poll_interval=0.0, background=False
        )
        stats = {}
        gen = serving.predict_rows(
            paged, [dict(r) for r in rows], mapping, batch_size=2,
            schedule="continuous", stats=stats, watcher=watcher,
            rollback_window=2,
        )
        out = [next(gen)]  # row 0 (budget 2) completes pre-swap
        ckpt.publish_for_serving(root, 5, params_b)
        out.extend(gen)
        assert len(out) == len(rows)
        assert all("error" not in r for r in out)
        assert stats["swaps"] == 1
        requeued = set(stats["swap_events"][0]["requeued"])
        for idx, committed in stats["swap_events"][0]["requeued"].items():
            np.testing.assert_array_equal(
                np.asarray(out[idx]["generated"])[:committed],
                np.asarray(ref_a[idx]["generated"])[:committed],
            )
        for i in range(len(rows)):
            if i == 0 or i in requeued:
                continue
            np.testing.assert_array_equal(
                np.asarray(out[i]["generated"]),
                np.asarray(ref_b[i]["generated"]), err_msg=str(i),
            )
        # restore generation A for the memoized decoder
        paged.make_slot_decoder(2).swap_weights(params_a)

    def test_int4_weights_paged_matches_int4_contiguous(self):
        # int4 weights (group-wise packed) on the paged layout: both
        # layouts dequantize the SAME packed tree, so tokens match
        big = dict(FLAGSHIP, vocab_size=256, embed_dim=64, mlp_dim=128)
        rows = _shared_rows(6, vocab=256)
        _, contig = _gen_predict(
            extra={"weights": "int4"}, tiny=big
        )
        ref, _ = _run(contig, rows)
        _, paged = _gen_predict(
            extra={"weights": "int4", "kv_layout": "paged"}, tiny=big
        )
        got, _ = _run(paged, rows)
        _assert_rows_equal(got, ref)
        dec = paged.make_slot_decoder(3)
        from tensorflowonspark_tpu import quantize as qz

        assert dec._quantized and dec._wq == "int4"
        assert qz.quantization_of(dec._qparams) == "int4"


# ----------------------------------------------------------------------
# the layout's raison d'être: zero-copy admits + physical sharing
# ----------------------------------------------------------------------


class TestZeroCopy:
    def test_cached_admit_is_one_dispatch_and_pages_shared(self):
        rows = _shared_rows(8)
        _, contig = _gen_predict(extra=CONTIG)
        _, paged = _gen_predict(extra=PAGED)
        dec_c = contig.make_slot_decoder(3)
        dec_p = paged.make_slot_decoder(3)
        shared = rows[0]["prompt"][:24]
        prompts = [np.concatenate([shared, np.full((i + 2,), i, np.int32)])
                   for i in range(3)]
        for dec in (dec_c, dec_p):
            dec.reset()
            for slot, p in enumerate(prompts):
                dec.admit(slot, p)
        # contiguous cached admit: install + prefill (+ extract when
        # new blocks commit); paged: ONE fused dispatch, always
        assert dec_p.last_admit_dispatches == 1
        assert dec_c.last_admit_dispatches >= 2
        # one physical page serves >= 2 slots simultaneously —
        # refcount-asserted through the pool (the acceptance bar)
        tables = dec_p.tables
        shared_pages = (
            set(tables[0][:3]) & set(tables[1][:3]) & set(tables[2][:3])
        )
        assert shared_pages, tables[:, :3]
        for pg in shared_pages:
            # 3 slots + the radix cache's own reference
            assert dec_p.page_pool.refcount(pg) >= 3
        st = dec_p.page_pool.stats()
        assert st["pool_pages_shared"] >= len(shared_pages)
        dec_p.reset()
        dec_c.reset()

    def test_evict_releases_and_trash_parks_table(self):
        _, paged = _gen_predict(extra=PAGED)
        dec = paged.make_slot_decoder(3)
        dec.reset()
        prompt = np.arange(20, dtype=np.int32) % 64
        dec.admit(0, prompt)
        used = dec.page_pool.stats()["pool_pages_used"]
        assert used > 0
        held = list(dec._slot_pages[0])
        dec.evict(0)
        assert dec._slot_pages[0] == []
        assert (dec.tables[0] == 0).all()  # parked on the trash page
        # committed (radix-held) pages survive; private ones freed
        for pg in held:
            assert dec.page_pool.refcount(pg) in (0, 1)
        dec.reset()

    def test_census_admission_count_independent(self):
        rows = _shared_rows(8)
        _, paged = _gen_predict(extra=PAGED)
        _run(paged, rows)
        dec = paged.make_slot_decoder(3)
        counts = dec.compile_counts()
        assert counts["prefill"] == 0       # classic path never used
        assert "install" not in counts      # no install program AT ALL
        assert "extract" not in counts      # no extract program AT ALL
        _run(paged, _shared_rows(12, seed=5))
        assert dec.compile_counts() == counts

    def test_engine_stats_carry_layout_and_pool_gauges(self):
        rows = _shared_rows(6)
        _, paged = _gen_predict(extra=PAGED)
        _, stats = _run(paged, rows)
        assert stats["kv_layout"] == "paged"
        assert stats["pool_pages"] > 0
        assert "pool_pages_shared" in stats
        _, contig = _gen_predict(extra=CONTIG)
        _, cstats = _run(contig, rows)
        assert cstats["kv_layout"] == "contiguous"
        assert "pool_pages" not in cstats

    def test_pool_pressure_evicts_radix_blocks(self):
        # a pool sized barely past the slots' own span: admits must
        # evict cold radix leaves to free pages, never deadlock
        _, paged = _gen_predict(extra=dict(PAGED, kv_pages=None,
                                           prefix_mem_mb=0.004))
        dec = paged.make_slot_decoder(3)
        rows = _shared_rows(10)
        _, contig = _gen_predict(extra=dict(CONTIG, prefix_mem_mb=0.004))
        ref, _ = _run(contig, rows)
        got, _ = _run(paged, rows)
        _assert_rows_equal(got, ref)
        assert dec.prefix_cache.evictions >= 0  # thrash is legal


# ----------------------------------------------------------------------
# allocator unit tests
# ----------------------------------------------------------------------


class TestPagePool:
    def test_alloc_retain_release_refcounts(self):
        pool = PagePool(6, reserved=1)
        a = pool.alloc(2)
        assert sorted(a) == [1, 2] or len(a) == 2
        pool.retain(a)
        assert all(pool.refcount(p) == 2 for p in a)
        pool.release(a)
        assert all(pool.refcount(p) == 1 for p in a)
        pool.release(a)
        assert pool.available() == 5
        with pytest.raises(ValueError):
            pool.release(a)

    def test_exhaustion_raises(self):
        pool = PagePool(4, reserved=1)
        pool.alloc(3)
        with pytest.raises(PoolExhausted):
            pool.alloc(1)

    def test_reserved_trash_page_never_alloced(self):
        pool = PagePool(5, reserved=1)
        assert 0 not in pool.alloc(4)

    def test_stats_shared_count(self):
        pool = PagePool(5)
        a = pool.alloc(2)
        pool.retain(a[:1])
        st = pool.stats()
        assert st["pool_pages_used"] == 2
        assert st["pool_pages_shared"] == 1

    def test_radix_release_fn_frees_pages(self):
        pool = PagePool(8)
        released = []
        pc = PrefixCache(block_tokens=4, mem_budget_bytes=1 << 20,
                         release_fn=lambda p: released.append(p))
        pages = pool.alloc(2)
        committed = []
        pc.insert(np.arange(8, dtype=np.int32), pages, 0, 100,
                  on_insert=committed.append)
        assert committed == pages
        pc.clear()
        # clear evicts leaf-up, so compare as sets
        assert sorted(released) == sorted(pages)


# ----------------------------------------------------------------------
# construction guards
# ----------------------------------------------------------------------


class TestGuards:
    def _model_params(self):
        model = tr.Transformer(tr.TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
            embed_dim=16, mlp_dim=32, max_seq_len=64, dtype="float32",
        ))
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        return model, params

    def test_bad_layout_rejected(self):
        model, params = self._model_params()
        with pytest.raises(ValueError, match="kv_layout"):
            tr.SlotDecoder(model, params, 2, 4, kv_layout="torn")

    def test_page_tokens_must_match_radix_block(self):
        model, params = self._model_params()
        pc = PrefixCache(block_tokens=8)
        with pytest.raises(ValueError, match="block_tokens"):
            tr.SlotDecoder(model, params, 2, 4, prefix_cache=pc,
                           kv_layout="paged", page_tokens=16)

    def test_kv_pages_floor_enforced(self):
        model, params = self._model_params()
        with pytest.raises(ValueError, match="kv_pages"):
            tr.SlotDecoder(model, params, 2, 4, kv_layout="paged",
                           kv_pages=3)

    def test_shared_radix_across_pools_rejected(self):
        model, params = self._model_params()
        pc = PrefixCache(block_tokens=16)
        tr.SlotDecoder(model, params, 2, 4, prefix_cache=pc,
                       kv_layout="paged")
        with pytest.raises(ValueError, match="page pool"):
            tr.SlotDecoder(model, params, 2, 4, prefix_cache=pc,
                           kv_layout="paged")


#: a tile-legal small shape (head_dim 128, banks of three 128-token
#: blocks): contiguous banks whose single-token decode steps the
#: block-walking kernel can take
TILE_LEGAL = {
    "vocab_size": 64, "num_layers": 2, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 128, "embed_dim": 32, "mlp_dim": 64,
    "max_seq_len": 384, "dtype": "float32", "attention_window": 200,
}


class TestBankKernelEngages:
    """Contiguous banks read through the decode kernel where the code
    can see it is legal, and through ``dot_attention`` elsewhere —
    chosen from shapes, no knob — with the same tokens either way."""

    @pytest.mark.parametrize("how, attn", [
        ("plain", "kernel"), ("mesh", "dot"), ("two_token_span", "dot"),
    ])
    def test_engine_reports_what_it_attends_with(self, how, attn):
        model = tr.Transformer(tr.TransformerConfig(**TILE_LEGAL))
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        extra = {}
        if how == "mesh":
            extra["tp"] = 2
        if how == "two_token_span":
            # a draft of one token: the verify block is a 2-token span
            extra.update(
                draft_config=dict(TILE_LEGAL, num_layers=1), draft_len=1,
                draft_params={
                    "embedding": params["embedding"],
                    "block_0": params["block_0"],
                    "ln_f": params["ln_f"], "lm_head": params["lm_head"],
                },
            )
        predict = tr.serving_builder(params, dict(
            TILE_LEGAL, mode="generate", max_new_tokens=5,
            pad_multiple=16, **extra
        ))
        rng = np.random.RandomState(0)
        rows = [
            {"prompt": rng.randint(0, 64, (n,)).astype(np.int32)}
            for n in (3, 17, 40, 9, 150)
        ]
        from tensorflowonspark_tpu import telemetry

        tracer = telemetry.get_tracer()
        tracer.clear()
        got, stats = _run(predict, rows)
        assert stats["attn"] == attn
        # a prompt's span into K/V banks is masked dot attention
        # whatever the decode step reads with
        prefills = [s["attrs"] for s in tracer.spans()
                    if s["name"] == "prefill"]
        assert len(prefills) == 5
        assert {p["attn"] for p in prefills} == {"dot"}
        slots, bank = 3, stats["kv_bank_tokens"]
        if how == "two_token_span":
            assert bank == slots * (384 + 2)  # the verify block's slack
        else:
            assert bank == slots * 384
        if attn == "kernel":
            # whole 128-token blocks of the live spans, one block for
            # a lane nobody holds: never the banks whole here
            assert stats["kv_read_tokens"] % 128 == 0
            assert 0 < stats["kv_read_tokens"] < bank
        else:
            assert stats["kv_read_tokens"] == bank
        for row, out in zip(rows, got):
            ref = tr.generate(
                model, params, jnp.asarray(row["prompt"])[None], 5
            )
            np.testing.assert_array_equal(
                np.asarray(out["generated"]), np.asarray(ref)[0],
            )
