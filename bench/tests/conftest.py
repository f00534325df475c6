"""A cell at a size a CPU test can hold: the qwen1.5-1.8b configuration
with every width cut (2 layers, d 128, 4 heads of 32, vocab 1009), 4 slots,
16-token pages, and a chat mix cut to match.  The engine's kernels run on
the pure-JAX backends."""

import copy
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
# the widest gap the tiny cell's served tokens may show: served streams of
# ten seeds read 0 to 0.0032, the fp8 control 0.021 to 0.067 on the same
# requests, and 0.0073 on the sample that test_run.py checks
TINY_GAP_LIMIT = 0.005


def tiny_conf() -> dict:
    conf = json.loads((BENCH / "configs" / "qwen1.5-1.8b.json").read_text())
    conf.update(hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=2, vocab_size=1009, rope_theta=10000.0)
    conf["serving"].update(n_slots=4, max_len=96, cache_block=16)
    conf["check"] = {"max_logit_gap": TINY_GAP_LIMIT}
    return conf


def tiny_mix() -> dict:
    return {"prompt_tokens": {"lo": 8, "hi": 40},
            "output_tokens": {"lo": 4, "hi": 24},
            "sizes": 64, "sizes_seed": 3}


@pytest.fixture()
def conf():
    return copy.deepcopy(tiny_conf())
