"""A toy cell: the real training cell's traffic at sizes a CPU test
holds, held to the real cell's limits."""
from __future__ import annotations

import json
import time

import jax

from harness import spec

TINY = {
    "name": "tiny", "reference": "dense_gqa", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "attention_bias": True, "tie_word_embeddings": False,
}


def _json(*parts):
    return json.loads(spec.BENCH_DIR.joinpath(*parts).read_text())


def _metrics(*names):
    return [{"name": n, "unit": "-"} for n in names]


def cell(traffic_name: str, limits: dict, e2e: list, chips: int = 1,
         **overrides):
    traffic = dict(_json("traffic", f"{traffic_name}.json"), **overrides)
    return spec.Cell(f"toy.{traffic_name}", chips, "tiny", dict(TINY),
                     traffic_name, traffic, limits, e2e, [])


def train_cell(seq=64, **kw):
    return cell("train.4k", _json("limits", "yi-9b.train.4k.json"),
                _metrics("train_tokens_per_s", "setup_s"), seq=seq, **kw)


def measure(c, seed=12345678901, seconds=2.0, **kw):
    """A whole run of the cell ``c`` on its first ``c.chips`` devices."""
    import run
    return run.measure(c, seed, seconds, trace=False,
                       devices=jax.devices()[:c.chips],
                       t_start=time.perf_counter(), **kw)
