"""Multi-request generation SERVING: ragged prompts through predict_rows.

No reference analogue — the reference's serving path is batch transform
of fixed-shape rows (TFModel.scala); text generation and ragged request
batching don't exist there.  This app exports a Transformer for
serving, then feeds dict-rows whose prompts have DIFFERENT lengths
through ``serving.predict_rows``:

- each batch is LEFT-padded to a length bucket
  (``predict.column_padding`` / ``pad_multiple``) and the per-row pad
  counts ship alongside, so ``generate(pad_start=...)`` masks the pad
  cache slots — every row produces exactly what its unpadded prompt
  would (RoPE scores depend only on position differences;
  equivalence-tested in tests/test_models.py);
- rows stop individually at ``--eos_id`` inside the one compiled decode
  scan, and ``generated_len`` reports where;
- ``--quantize int8`` composes weight-only int8 + the int8 KV cache
  with GQA (``--num_kv_heads``) and sliding-window attention
  (``--attention_window``) — the full decode-efficiency stack in one
  serving config (its rate is not measured on the chip);
- ``--schedule continuous`` runs the same requests through the
  slot-level in-flight scheduler instead of static batches: finished
  rows are evicted and waiting prompts admitted into the freed
  KV-cache slots between chunked decode scans (docs/serving.md).

The export also writes ``output_schema`` into the serving metadata
(via ``serving.infer_output_schema``), so a distributed
``TFModel.transform`` over this export types its DataFrame without
the legacy one-row probe job.

Run (CPU or a real chip):

    python examples/transformer/serve_generate_tpu.py
    python examples/transformer/serve_generate_tpu.py \
        --quantize int8 --num_kv_heads 2 --attention_window 128 \
        --schedule continuous
"""

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--num_layers", type=int, default=4)
    p.add_argument("--num_heads", type=int, default=4)
    p.add_argument("--num_kv_heads", type=int, default=0)
    p.add_argument("--head_dim", type=int, default=32)
    p.add_argument("--embed_dim", type=int, default=128)
    p.add_argument("--mlp_dim", type=int, default=512)
    p.add_argument("--max_seq_len", type=int, default=512)
    p.add_argument("--attention_window", type=int, default=0)
    p.add_argument("--num_requests", type=int, default=12)
    p.add_argument("--min_prompt", type=int, default=4)
    p.add_argument("--max_prompt", type=int, default=48)
    p.add_argument("--max_new_tokens", type=int, default=24)
    p.add_argument("--batch_size", type=int, default=4)
    p.add_argument("--pad_multiple", type=int, default=16)
    p.add_argument("--eos_id", type=int, default=None)
    p.add_argument("--quantize", choices=["none", "int8"], default="none")
    p.add_argument("--schedule", choices=["static", "continuous"],
                   default="static")
    p.add_argument("--chunk_size", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.checkpoint import save_for_serving
    from tensorflowonspark_tpu.models import transformer as tr

    on_tpu = jax.default_backend() == "tpu"
    cfg = dict(
        vocab_size=args.vocab,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        num_kv_heads=args.num_kv_heads,
        head_dim=args.head_dim,
        embed_dim=args.embed_dim,
        mlp_dim=args.mlp_dim,
        max_seq_len=args.max_seq_len,
        dtype="bfloat16" if on_tpu else "float32",
        attention_window=args.attention_window,
        cache_dtype="int8" if args.quantize == "int8" else (
            "bfloat16" if on_tpu else "float32"
        ),
    )
    model = tr.Transformer(tr.TransformerConfig(**cfg))
    params = jax.jit(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"]
    )(jax.random.PRNGKey(args.seed))

    # export -> load: the full serving contract (model_ref metadata),
    # exactly what an inference fleet or the CLI consumes
    with tempfile.TemporaryDirectory() as tmp:
        export = os.path.join(tmp, "export")
        model_config = dict(
            cfg,
            mode="generate",
            max_new_tokens=args.max_new_tokens,
            pad_multiple=args.pad_multiple,
            chunk_size=args.chunk_size,
            max_prompt_len=args.max_prompt,
        )
        if args.eos_id is not None:
            model_config["eos_id"] = args.eos_id
        if args.quantize == "int8":
            model_config["quantize"] = "int8"
        np_params = jax.tree.map(np.asarray, params)
        # one tiny row through the predictor types the export: the
        # distributed transform reads output_schema from metadata
        # instead of probing (and re-decoding) partition 0
        schema = serving.infer_output_schema(
            tr.serving_builder(np_params, model_config),
            {"prompt": np.zeros((4,), np.int32)},
            {"prompt": "tokens"},
        )
        save_for_serving(
            export,
            np_params,
            extra_metadata={
                "model_ref":
                    "tensorflowonspark_tpu.models.transformer:"
                    "serving_builder",
                "model_config": model_config,
            },
            output_schema=schema,
        )
        predict = serving.load_predictor(export)

        rng = np.random.RandomState(args.seed)
        lens = rng.randint(
            args.min_prompt, args.max_prompt + 1, size=args.num_requests
        )
        rows = [
            {"prompt": rng.randint(0, args.vocab, (n,)).astype(np.int32)}
            for n in lens
        ]
        t0 = time.time()
        sched_stats = {}
        outs = list(serving.predict_rows(
            predict, rows, {"prompt": "tokens"},
            batch_size=args.batch_size,
            schedule=args.schedule, stats=sched_stats,
        ))
        dt = time.time() - t0
        for i, (n, o) in enumerate(zip(lens, outs)):
            gen = o["generated"]
            stop = o.get("generated_len")
            shown = gen if stop is None else gen[: int(stop)]
            print(
                "req %2d  prompt_len=%2d  ->  %s%s"
                % (
                    i, n, " ".join(str(int(t)) for t in shown[:12]),
                    " ..." if len(shown) > 12 else "",
                )
            )
        toks = args.num_requests * args.max_new_tokens
        print(
            "%d ragged requests (%d-%d tokens), %d generated tokens "
            "in %.2fs (%.0f tok/s incl. compile, %s schedule)"
            % (
                args.num_requests, int(lens.min()), int(lens.max()),
                toks, dt, toks / dt, args.schedule,
            )
        )
        if sched_stats.get("latency_sec"):
            lat = sorted(sched_stats["latency_sec"].values())
            print(
                "continuous: %d admitted / %d chunks, per-request "
                "p50=%.0fms p99=%.0fms"
                % (
                    sched_stats["admitted"], sched_stats["chunks"],
                    1e3 * lat[len(lat) // 2], 1e3 * lat[-1],
                )
            )


if __name__ == "__main__":
    main()
