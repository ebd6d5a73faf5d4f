"""Model-zoo shape/numerics tests (tiny configs, CPU).

Mirrors the reference's synthetic-data 1-step pattern
(reference: examples/resnet/resnet_cifar_test.py:36-40 runs the real
compiled model on synthetic inputs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import (
    MNISTNet,
    ResNet50,
    ResNetCIFAR,
    Transformer,
    TransformerConfig,
    UNet,
)


class TestMNISTNet:
    def test_forward_shape(self):
        model = MNISTNet(hidden=16)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 28, 28)))
        out = model.apply(params, jnp.zeros((5, 28, 28)))
        assert out.shape == (5, 10)


class TestResNet:
    def test_cifar_forward(self):
        model = ResNetCIFAR(depth=8, dtype="float32")
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
        out = model.apply(variables, jnp.zeros((2, 32, 32, 3)), train=False)
        assert out.shape == (2, 10)

    def test_cifar_depth56_block_count(self):
        model = ResNetCIFAR(depth=56, dtype="float32")
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
        blocks = [k for k in variables["params"] if k.startswith("stage")]
        assert len(blocks) == 27  # 3 stages x 9 blocks = (56-2)/6 per stage

    def test_resnet50_forward(self):
        model = ResNet50(num_classes=10, dtype="float32", stage_sizes=(1, 1, 1, 1))
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
        out = model.apply(variables, jnp.zeros((2, 64, 64, 3)), train=False)
        assert out.shape == (2, 10)


class TestUNet:
    def test_forward_shape(self):
        model = UNet(num_classes=3, base_filters=8, dtype="float32")
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 128, 128, 3)))
        out = model.apply(variables, jnp.zeros((2, 128, 128, 3)))
        assert out.shape == (2, 128, 128, 3)


class TestTransformer:
    def _tiny(self, **kw):
        cfg = TransformerConfig(
            vocab_size=64,
            num_layers=2,
            num_heads=2,
            head_dim=8,
            embed_dim=16,
            mlp_dim=32,
            dtype="float32",
            **kw,
        )
        return Transformer(cfg), cfg

    def test_forward_shape(self):
        model, _ = self._tiny()
        tokens = jnp.zeros((2, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        logits = model.apply({"params": params}, tokens)
        assert logits.shape == (2, 16, 64)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        """Changing a future token must not change past logits."""
        model, _ = self._tiny()
        rng = jax.random.PRNGKey(0)
        t1 = jax.random.randint(rng, (1, 12), 0, 64)
        t2 = t1.at[0, -1].set((t1[0, -1] + 1) % 64)
        params = model.init(rng, t1)["params"]
        l1 = model.apply({"params": params}, t1)
        l2 = model.apply({"params": params}, t2)
        np.testing.assert_allclose(
            np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]), atol=1e-5
        )
        assert not np.allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]))

    def test_decode_prefill_matches_full_forward(self):
        # KV-cache prefill over the prompt must reproduce the ordinary
        # forward's logits exactly (same math, cached keys)
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=32)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        full = model.apply({"params": params}, tokens)
        cache = tr.init_cache(model, 2)
        pre, _ = model.apply(
            {"params": params, "cache": cache}, tokens, decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(pre), np.asarray(full), atol=1e-5, rtol=1e-5
        )

    def test_decode_steps_match_full_forward(self):
        # feeding tokens one at a time through the cache must agree
        # with re-running the full forward at every length
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=32)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 0, 64)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        cache = tr.init_cache(model, 2)
        for t in range(tokens.shape[1]):
            step_logits, mut = model.apply(
                {"params": params, "cache": cache}, tokens[:, t:t + 1],
                decode=True, mutable=["cache"],
            )
            cache = mut["cache"]
            full = model.apply({"params": params}, tokens[:, :t + 1])
            np.testing.assert_allclose(
                np.asarray(step_logits[:, 0]), np.asarray(full[:, -1]),
                atol=1e-5, rtol=1e-5, err_msg="step %d" % t,
            )

    def test_generate_greedy_matches_full_forward_rollout(self):
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=32)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0, 64)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        got = tr.generate(model, params, prompt, max_new_tokens=8)
        assert got.shape == (2, 8)
        # reference rollout: full forward each step, greedy argmax
        seq = prompt
        ref = []
        for _ in range(8):
            logits = model.apply({"params": params}, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            ref.append(nxt)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(
            np.asarray(got), np.stack([np.asarray(r) for r in ref], axis=1)
        )

    def test_speculative_generate_is_lossless(self):
        # prompt-lookup speculation must reproduce vanilla greedy
        # decode token for token — acceptance only reorders the work
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        for b, seed in ((1, 4), (2, 5)):
            prompt = jax.random.randint(
                jax.random.PRNGKey(seed), (b, 10), 0, 64
            )
            params = model.init(jax.random.PRNGKey(0), prompt)["params"]
            ref = tr.generate(model, params, prompt, max_new_tokens=16)
            got, rounds = tr.generate_speculative(
                model, params, prompt, 16, draft_len=4, ngram=2,
                return_stats=True,
            )
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
            assert 1 <= int(rounds) <= 16

    def test_speculative_accepts_on_repetitive_input(self):
        # a perfectly periodic prompt: the n-gram draft should keep
        # matching, so verify rounds << tokens generated
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=96)
        prompt = jnp.asarray(
            np.tile(np.arange(6), 6)[None, :], jnp.int32
        )
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        ref = tr.generate(model, params, prompt, max_new_tokens=24)
        got, rounds = tr.generate_speculative(
            model, params, prompt, 24, draft_len=4, ngram=2,
            return_stats=True,
        )
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        assert int(rounds) < 24  # strictly fewer forwards than tokens

    def test_ragged_generate_matches_per_row(self):
        # ragged multi-request batching: left-padded
        # rows with pad_start must generate exactly what each row's
        # unpadded prompt generates alone (greedy; RoPE scores depend
        # only on position differences, so physical-slot positions
        # leave per-row numerics identical)
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        rng = np.random.RandomState(11)
        lens = [5, 9, 3]
        p_max = max(lens)
        prompts = [
            rng.randint(0, 64, (n,)).astype(np.int32) for n in lens
        ]
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, p_max), jnp.int32)
        )["params"]

        padded = np.zeros((len(lens), p_max), np.int32)
        for i, p in enumerate(prompts):
            padded[i, p_max - len(p):] = p
        pad_start = jnp.asarray(
            [p_max - n for n in lens], jnp.int32
        )
        got = tr.generate(
            model, params, jnp.asarray(padded), 6, pad_start=pad_start
        )
        for i, p in enumerate(prompts):
            want = tr.generate(model, params, jnp.asarray(p[None]), 6)
            np.testing.assert_array_equal(
                np.asarray(got[i]), np.asarray(want[0]),
                err_msg="row %d (len %d)" % (i, len(p)),
            )

    def test_generate_eos_stops_row(self):
        # once a row samples eos_id, every later position repeats it
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0, 64)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        free = tr.generate(model, params, prompt, 10)
        # pick row 0's third emitted token as the stop token
        eos = int(free[0, 2])
        got = np.asarray(
            tr.generate(model, params, prompt, 10, eos_id=eos)
        )
        for r in range(got.shape[0]):
            hits = np.where(got[r] == eos)[0]
            if hits.size:
                assert (got[r, hits[0]:] == eos).all(), got[r]
        # row 0 must stop at position 2 and match the free run before it
        np.testing.assert_array_equal(got[0, :3], np.asarray(free[0, :3]))
        assert (got[0, 2:] == eos).all()

    def test_serving_ragged_generate_end_to_end(self):
        # predict_rows + column_padding: ragged dict-rows in, per-row
        # generations out, matching direct unpadded generate
        from tensorflowonspark_tpu import serving
        from tensorflowonspark_tpu.models import transformer as tr

        model, cfg = self._tiny(max_seq_len=96)
        rng = np.random.RandomState(13)
        lens = [4, 7, 11, 2, 9]
        prompts = [
            rng.randint(0, 64, (n,)).astype(np.int32) for n in lens
        ]
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        predict = tr.serving_builder(
            jax.tree.map(np.asarray, params),
            {
                "vocab_size": 64, "num_layers": 2, "num_heads": 2,
                "head_dim": 8, "embed_dim": 16, "mlp_dim": 32,
                "max_seq_len": 96, "dtype": "float32",
                "mode": "generate", "max_new_tokens": 5,
                "pad_multiple": 16,
            },
        )
        rows = [{"prompt": p} for p in prompts]
        out = list(serving.predict_rows(
            predict, rows, {"prompt": "tokens"}, batch_size=3
        ))
        assert len(out) == len(prompts)
        for i, p in enumerate(prompts):
            want = tr.generate(model, params, jnp.asarray(p[None]), 5)
            np.testing.assert_array_equal(
                np.asarray(out[i]["generated"]), np.asarray(want[0]),
                err_msg="row %d (len %d)" % (i, len(p)),
            )

    def test_generated_len_matches_first_eos(self):
        # the eos contract (generate() docstring): serving emits rows
        # UNTRIMMED at [B, max_new] plus a generated_len column equal
        # to the FIRST eos position (max_new when no eos); the consumer
        # trims row[:generated_len]
        from tensorflowonspark_tpu import serving
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        prompts = [
            np.asarray(p, np.int32)
            for p in (
                jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0, 64)
            )
        ]
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 6), jnp.int32)
        )["params"]
        free = np.asarray(
            tr.generate(model, params, jnp.asarray(prompts[0][None]), 10)
        )
        eos = int(free[0, 2])
        predict = tr.serving_builder(
            jax.tree.map(np.asarray, params),
            {
                "vocab_size": 64, "num_layers": 2, "num_heads": 2,
                "head_dim": 8, "embed_dim": 16, "mlp_dim": 32,
                "max_seq_len": 64, "dtype": "float32",
                "mode": "generate", "max_new_tokens": 10,
                "pad_multiple": 8, "eos_id": eos,
            },
        )
        out = list(serving.predict_rows(
            predict, [{"prompt": p} for p in prompts],
            {"prompt": "tokens"}, batch_size=2,
        ))
        for r in out:
            gen = np.asarray(r["generated"])
            n = int(r["generated_len"])
            assert gen.shape == (10,)  # untrimmed: static scan shape
            hits = np.where(gen == eos)[0]
            assert n == (int(hits[0]) if hits.size else 10)
            # everything from the first eos on is eos (consumer trims)
            if hits.size:
                assert (gen[n:] == eos).all()
        # row 0 stops where the free run first emitted the eos value
        assert int(out[0]["generated_len"]) == int(
            np.where(free[0] == eos)[0][0]
        )

    def test_speculative_input_validation(self):
        # ADVICE r4: max_new_tokens<=0 early-returns [B, 0] without
        # allocating a cache; ngram<1 raises (ngram=0 made every
        # history position match)
        import pytest as _pytest

        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, 64)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        out = tr.generate_speculative(model, params, prompt, 0)
        assert out.shape == (2, 0)
        out, rounds = tr.generate_speculative(
            model, params, prompt, -3, return_stats=True
        )
        assert out.shape == (2, 0) and rounds == 0
        with _pytest.raises(ValueError, match="ngram"):
            tr.generate_speculative(model, params, prompt, 8, ngram=0)

    def test_speculative_draft_model_is_lossless_with_stats(self):
        # a DRAFT MODEL replaces prompt lookup: outputs must still be
        # the exact greedy chain whatever the draft proposes, and the
        # accept accounting must calibrate (self-draft -> rate 1.0)
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        draft_model, _ = self._tiny(max_seq_len=64)
        prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 10), 0, 64)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        dparams = draft_model.init(jax.random.PRNGKey(9), prompt)["params"]
        ref = tr.generate(model, params, prompt, max_new_tokens=12)
        st = {}
        got, rounds = tr.generate_speculative(
            model, params, prompt, 12, draft_len=4,
            draft_model=draft_model, draft_params=dparams,
            return_stats=True, stats=st,
        )
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        assert st["rounds"] == int(rounds)
        assert st["proposed"] == 4 * st["rounds"]
        assert 0.0 <= st["accept_rate"] <= 1.0
        # self-draft: every proposal verifies
        st = {}
        got = tr.generate_speculative(
            model, params, prompt, 12, draft_len=4,
            draft_model=model, draft_params=params, stats=st,
        )
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        assert st["accept_rate"] == 1.0
        assert st["rounds"] < 12  # strictly fewer verifies than tokens

    def test_speculative_draft_vocab_mismatch_raises(self):
        import pytest as _pytest

        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        bad = tr.Transformer(tr.TransformerConfig(
            vocab_size=32, num_layers=1, num_heads=2, head_dim=8,
            embed_dim=16, mlp_dim=32, max_seq_len=64, dtype="float32",
        ))
        prompt = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        bparams = bad.init(jax.random.PRNGKey(1), prompt)["params"]
        with _pytest.raises(ValueError, match="vocab"):
            tr.generate_speculative(
                model, params, prompt, 8, draft_model=bad,
                draft_params=bparams,
            )
        with _pytest.raises(ValueError, match="draft_params"):
            tr.generate_speculative(
                model, params, prompt, 8, draft_model=bad,
            )

    def test_speculative_composes_with_quantized_weights(self):
        from tensorflowonspark_tpu import quantize as qz
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=64)
        prompt = jax.random.randint(jax.random.PRNGKey(6), (1, 8), 0, 64)
        params = jax.tree.map(
            lambda x: x * 3.0,
            model.init(jax.random.PRNGKey(0), prompt)["params"],
        )
        ref = tr.generate_speculative(model, params, prompt, 8)
        got = tr.generate_speculative(
            model, qz.quantize_tree(params, min_size=512), prompt, 8
        )
        # decisive params: int8 noise must not flip the first tokens
        np.testing.assert_array_equal(
            np.asarray(ref)[:, 0], np.asarray(got)[:, 0]
        )

    def test_generate_capacity_and_sampling_guards(self):
        import pytest as _pytest

        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(max_seq_len=16)
        prompt = jnp.zeros((1, 10), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        with _pytest.raises(ValueError, match="capacity"):
            tr.generate(model, params, prompt, max_new_tokens=8)
        with _pytest.raises(ValueError, match="rng"):
            tr.generate(
                model, params, prompt, max_new_tokens=2, temperature=1.0
            )
        # temperature sampling: deterministic under one key, in-vocab
        out = tr.generate(
            model, params, prompt, max_new_tokens=4, temperature=1.0,
            rng=jax.random.PRNGKey(7),
        )
        out2 = tr.generate(
            model, params, prompt, max_new_tokens=4, temperature=1.0,
            rng=jax.random.PRNGKey(7),
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
        assert int(jnp.max(out)) < 64 and out.shape == (1, 4)

    def test_gqa_matches_repeated_kv_weights(self):
        # a GQA model with kv weights TILED to full heads must equal
        # the MHA model: grouped attention == repeat-kv attention
        from tensorflowonspark_tpu.models import transformer as tr

        gqa, _ = self._tiny(num_kv_heads=1)
        mha, _ = self._tiny()
        tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, 64)
        p_gqa = gqa.init(jax.random.PRNGKey(0), tokens)["params"]
        p_mha = jax.tree.map(lambda x: x, p_gqa)  # copy structure
        for i in range(2):
            blk = p_mha["block_%d" % i]["attn"]
            blk["k"] = {"kernel": jnp.tile(
                p_gqa["block_%d" % i]["attn"]["k"]["kernel"], (1, 2, 1)
            )}
            blk["v"] = {"kernel": jnp.tile(
                p_gqa["block_%d" % i]["attn"]["v"]["kernel"], (1, 2, 1)
            )}
        np.testing.assert_allclose(
            np.asarray(gqa.apply({"params": p_gqa}, tokens)),
            np.asarray(mha.apply({"params": p_mha}, tokens)),
            atol=1e-5, rtol=1e-5,
        )

    def test_gqa_decode_matches_full_forward(self):
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(num_kv_heads=1, max_seq_len=32)
        tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 10), 0, 64)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        full = model.apply({"params": params}, tokens)
        cache = tr.init_cache(model, 2)
        # cache banks carry the REDUCED kv head count
        banks = [
            x for x in jax.tree.leaves(cache) if getattr(x, "ndim", 0) == 4
        ]
        assert all(b.shape[2] == 1 for b in banks)
        pre, _ = model.apply(
            {"params": params, "cache": cache}, tokens, decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(pre), np.asarray(full), atol=1e-5, rtol=1e-5
        )

    def test_windowed_decode_matches_full_forward(self):
        # sliding-window model: the decode-cache mask must apply the
        # same horizon as the training-time mask
        from tensorflowonspark_tpu.models import transformer as tr

        model, _ = self._tiny(attention_window=5, max_seq_len=32)
        tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 14), 0, 64)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        full = model.apply({"params": params}, tokens)
        cache = tr.init_cache(model, 2)
        pre, _ = model.apply(
            {"params": params, "cache": cache}, tokens, decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(pre), np.asarray(full), atol=1e-5, rtol=1e-5
        )

    def test_gqa_rejects_bad_head_counts(self):
        import pytest as _pytest

        model, _ = self._tiny(num_kv_heads=3)  # 2 heads % 3 != 0
        tokens = jnp.zeros((1, 8), jnp.int32)
        with _pytest.raises(ValueError, match="divide"):
            model.init(jax.random.PRNGKey(0), tokens)
        fused, _ = self._tiny(num_kv_heads=1, fused_qkv=True)
        with _pytest.raises(ValueError, match="fused_qkv"):
            fused.init(jax.random.PRNGKey(0), tokens)

    def test_sample_logits_filters(self):
        from tensorflowonspark_tpu.models import transformer as tr

        logits = jnp.asarray(
            [[4.0, 3.0, 2.0, 1.0, 0.0], [0.0, 1.0, 2.0, 3.0, 4.0]]
        )
        key = jax.random.PRNGKey(0)
        # temperature 0 = greedy
        np.testing.assert_array_equal(
            np.asarray(tr.sample_logits(logits, key)), [0, 4]
        )
        # top_k=1 collapses sampling to greedy at any temperature
        np.testing.assert_array_equal(
            np.asarray(
                tr.sample_logits(logits, key, temperature=5.0, top_k=1)
            ),
            [0, 4],
        )
        # tiny top_p keeps only the top token
        np.testing.assert_array_equal(
            np.asarray(
                tr.sample_logits(logits, key, temperature=5.0, top_p=1e-6)
            ),
            [0, 4],
        )
        # top_k=2: every sample must come from the two highest logits
        keys = jax.random.split(jax.random.PRNGKey(1), 64)
        draws = np.stack([
            np.asarray(
                tr.sample_logits(logits, k, temperature=2.0, top_k=2)
            )
            for k in keys
        ])
        assert set(draws[:, 0]) <= {0, 1}
        assert set(draws[:, 1]) <= {3, 4}

    def test_loss_decreases(self):
        import optax

        from tensorflowonspark_tpu.models import transformer as tr
        from tensorflowonspark_tpu.parallel import dp

        model, _ = self._tiny()
        tokens = (jnp.arange(8 * 16) % 7).reshape(8, 16).astype(jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        trainer = dp.SyncTrainer(tr.loss_fn(model), optax.adam(1e-2))
        state = trainer.create_state(params)
        losses = []
        for i in range(8):
            state, m = trainer.step(state, {"tokens": tokens})
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_fused_qkv_matches_unfused(self):
        """One [embed -> 3,H,D] projection is numerically identical to
        three separate q/k/v matmuls when fed the same weights."""
        model_f, _ = self._tiny(fused_qkv=True)
        model_u, _ = self._tiny(fused_qkv=False)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        pf = model_f.init(jax.random.PRNGKey(0), tokens)["params"]
        pu = jax.tree.map(lambda x: x, model_u.init(
            jax.random.PRNGKey(0), tokens
        )["params"])
        # graft the fused kernel's three slices into the unfused tree
        for blk in ("block_0", "block_1"):
            kern = pf[blk]["attn"]["qkv"]["kernel"]  # [Dm, 3, H, D]
            for i, name in enumerate(("q", "k", "v")):
                pu[blk]["attn"][name]["kernel"] = kern[:, i]
            for shared in ("out",):
                pu[blk]["attn"][shared] = pf[blk]["attn"][shared]
            for other in ("ln1", "ln2", "mlp"):
                pu[blk][other] = pf[blk][other]
        for top in ("embedding", "ln_f", "lm_head"):
            pu[top] = pf[top]
        np.testing.assert_allclose(
            np.asarray(model_f.apply({"params": pf}, tokens)),
            np.asarray(model_u.apply({"params": pu}, tokens)),
            atol=1e-5,
        )

    def test_remat_policy_invariant(self):
        """remat (block or dots policy) must not change the forward."""
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        base, _ = self._tiny(remat=False)
        params = base.init(jax.random.PRNGKey(0), tokens)["params"]
        ref = base.apply({"params": params}, tokens)
        for policy in ("block", "dots"):
            m, _ = self._tiny(remat=True, remat_policy=policy)
            np.testing.assert_allclose(
                np.asarray(m.apply({"params": params}, tokens)),
                np.asarray(ref),
                atol=1e-6,
            )
        with pytest.raises(ValueError, match="remat_policy"):
            m, _ = self._tiny(remat=True, remat_policy="nope")
            m.apply({"params": params}, tokens)

    def test_logical_axes_cover_params(self):
        from tensorflowonspark_tpu.models import transformer as tr
        from tensorflowonspark_tpu.parallel import sharding as sh
        from tensorflowonspark_tpu.parallel.mesh import build_mesh

        model, _ = self._tiny()
        tokens = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        ann = tr.logical_axes(params)
        m = build_mesh({"data": 2, "fsdp": 2, "model": 2})
        specs = sh.param_specs(params, sh.RULES_TP_FSDP, m, ann)
        # the TP-critical kernels must actually shard on 'model'
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        sharded = {
            "/".join(str(getattr(p, "key", p)) for p in path): spec
            for path, spec in flat
        }
        assert any(
            "model" in str(spec)
            for path, spec in sharded.items()
            if "mlp" in path or "attn" in path
        )


def test_serving_builders_roundtrip(tmp_path):
    # every zoo model exposes a model_ref-compatible serving builder
    import jax
    import numpy as np

    from tensorflowonspark_tpu.models import mlp, resnet, transformer, unet

    # mlp
    m = mlp.MNISTNet(hidden=16)
    p = m.init(jax.random.PRNGKey(0), np.zeros((1, 784), np.float32))["params"]
    predict = mlp.serving_builder(
        jax.tree.map(np.asarray, p), {"hidden": 16}
    )
    out = predict({"image": np.zeros((2, 784), np.float32)})
    assert out["prediction"].shape == (2,)

    # resnet (batch_stats included)
    rm = resnet.ResNetCIFAR(depth=8, num_classes=10, dtype="float32")
    rv = rm.init(jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32))
    predict = resnet.serving_builder(
        jax.tree.map(np.asarray, dict(rv)), {"depth": 8}
    )
    out = predict({"image": np.zeros((2, 32, 32, 3), np.float32)})
    assert out["logits"].shape == (2, 10)

    # unet
    um = unet.UNet(num_classes=3, base_filters=4)
    uv = um.init(jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32))
    predict = unet.serving_builder(
        jax.tree.map(np.asarray, dict(uv)), {"num_classes": 3, "base_filters": 4}
    )
    out = predict({"image": np.zeros((2, 32, 32, 3), np.float32)})
    assert out["mask"].shape == (2, 32, 32)

    # transformer
    cfg = dict(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
               embed_dim=16, mlp_dim=32, dtype="float32")
    tm = transformer.Transformer(transformer.TransformerConfig(**cfg))
    tp = tm.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    predict = transformer.serving_builder(jax.tree.map(np.asarray, tp), cfg)
    out = predict({"tokens": np.zeros((2, 8), np.int64)})
    assert out["logits"].shape == (2, 8, 64)
    assert out["next_token"].shape == (2,)

    # transformer generation mode: prompt batch in -> greedy
    # continuations out, equal to calling generate() directly
    import jax.numpy as jnp

    gen_predict = transformer.serving_builder(
        jax.tree.map(np.asarray, tp),
        dict(cfg, mode="generate", max_new_tokens=5),
    )
    prompt = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int64)
    gout = gen_predict({"tokens": prompt})
    assert gout["generated"].shape == (2, 5)
    direct = transformer.generate(
        tm, tp, jnp.asarray(prompt, jnp.int32), 5
    )
    np.testing.assert_array_equal(gout["generated"], np.asarray(direct))


def test_transformer_ring_matches_dot_logits():
    # model-level SP correctness: ring-attention transformer == dense
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models import transformer as tr
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"data": 2, "seq": 4})
    base = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                embed_dim=32, mlp_dim=64, dtype="float32")
    m_dot = tr.Transformer(tr.TransformerConfig(**base))
    m_ring = tr.Transformer(
        tr.TransformerConfig(**base, attention_impl="ring", mesh=mesh)
    )
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, size=(4, 32)), jnp.int32
    )
    params = m_dot.init(jax.random.PRNGKey(0), tokens)["params"]
    out_dot = m_dot.apply({"params": params}, tokens)
    out_ring = m_ring.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(out_dot), np.asarray(out_ring), rtol=2e-4, atol=2e-4
    )


def test_serving_builder_guards():
    # resnet without batch_stats fails with a clear message; transformer
    # ring config serves via dense attention
    import jax
    import numpy as np
    import pytest as _pytest

    from tensorflowonspark_tpu.models import resnet, transformer

    rm = resnet.ResNetCIFAR(depth=8, dtype="float32")
    rv = rm.init(jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32))
    with _pytest.raises(ValueError, match="batch_stats"):
        resnet.serving_builder(
            jax.tree.map(np.asarray, dict(rv))["params"], {"depth": 8}
        )

    cfg = dict(vocab_size=32, num_layers=1, num_heads=2, head_dim=4,
               embed_dim=8, mlp_dim=16, dtype="float32",
               attention_impl="ring")
    tm = transformer.Transformer(
        transformer.TransformerConfig(
            **{k: v for k, v in cfg.items() if k != "attention_impl"}
        )
    )
    tp = tm.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    predict = transformer.serving_builder(jax.tree.map(np.asarray, tp), cfg)
    out = predict({"tokens": np.zeros((2, 8), np.int64)})
    assert out["logits"].shape == (2, 8, 32)


def test_resnet50_s2d_stem_exact_equivalence():
    # space-to-depth stem == conv7x7/s2 stem exactly, via the kernel
    # transform (the MXU-friendly MLPerf stem; models/resnet.py)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.models import resnet

    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    m7 = resnet.ResNet50(
        num_classes=10, dtype="float32", stage_sizes=(1,), stem="conv7"
    )
    ms = resnet.ResNet50(
        num_classes=10, dtype="float32", stage_sizes=(1,), stem="s2d"
    )
    v7 = m7.init(jax.random.PRNGKey(0), x)
    p7 = dict(v7["params"])
    ps = dict(p7)
    ps["stem_conv"] = {
        "kernel": resnet.conv7_to_s2d_kernel(p7["stem_conv"]["kernel"])
    }
    out7 = m7.apply(
        {"params": p7, "batch_stats": v7["batch_stats"]}, x, train=False
    )
    outs = ms.apply(
        {"params": ps, "batch_stats": v7["batch_stats"]}, x, train=False
    )
    np.testing.assert_allclose(
        np.asarray(out7), np.asarray(outs), atol=1e-5
    )
