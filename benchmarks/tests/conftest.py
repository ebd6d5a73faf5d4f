"""The benchmark's own tests run on the CPU, from the repository's
root: ``python -m pytest benchmarks/tests -q``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: a width-for-width miniature of the published block, for CPU tests
TINY_MODEL = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
    sliding_window=48, max_position_embeddings=256,
)
TINY_SERVE = dict(
    config=TINY_MODEL,
    traffic=dict(
        clients=4, warm_in_s=2.0, check_sample=3,
        requests_per_client=400,
        prompt_tokens={"dist": "loguniform", "lo": 8, "hi": 24},
        answer_tokens={"dist": "loguniform", "lo": 8, "hi": 40},
    ),
)
TINY_TRAIN = dict(
    config=TINY_MODEL,
    traffic=dict(
        seq_len=128,
        documents={"dist": "lognormal", "median": 30, "sigma": 1.0,
                   "lo": 4, "hi": 128},
    ),
)
