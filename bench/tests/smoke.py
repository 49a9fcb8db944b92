"""Cells at small widths for the CPU tests: the benchmark's own
configuration files and mixes with widths, lengths and slots cut down,
float32 throughout, the Pallas kernels interpreted."""

from __future__ import annotations

import dataclasses

from bench import harness, session
from bench.traffic import generator

WIDTHS = {
    "hidden_size": 256,
    "intermediate_size": 512,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 64,
    "vocab_size": 512,
    "torch_dtype": "float32",
}
PROGRAM = {
    "num_layers": 2,
    "d_model": 256,
    "num_heads": 4,
    "num_kv_heads": 2,
    "head_dim": 64,
    "d_ff": 512,
    "vocab_size": 512,
    "dtype": "float32",
    "attn_chunk": 128,
}
BLOCK = 64
MIXES = {
    "chat": generator.Mix(
        name="chat-smoke",
        arrivals={"kind": "poisson", "rate_per_s": 6.0},
        prompt_len={"median": 96, "sigma": 0.8, "min": 16, "max": 256},
        output_len={"median": 8, "sigma": 0.7, "min": 2, "max": 24},
        engine={"max_slots": 4, "max_len": 320, "max_prefill_batch": 2},
    ),
    # an offline backlog that outlasts a short window: long prompts
    # and answers for the size, a first wave admitted in set-up
    "longctx": generator.Mix(
        name="longctx-smoke",
        arrivals={"kind": "backlog", "count": 160},
        prompt_len={"median": 160, "sigma": 0.5, "min": 96, "max": 256},
        output_len={"median": 24, "sigma": 0.6, "min": 8, "max": 48},
        engine={"max_slots": 4, "max_len": 320, "max_prefill_batch": 1},
    ),
}


def cell(like: str, mix: str, limit: float = 0.05) -> harness.Cell:
    """The benchmark's cell ``like`` (its configuration and metric
    lists) under the cut-down mix ``mix``."""
    base = harness.resolve_cell(harness.load_benchmark(), like)
    return dataclasses.replace(
        base,
        name=f"{like}-{mix}-smoke",
        mix=MIXES[mix],
        cell={"served_logit_gap_limit": limit},
    )


def options(c: harness.Cell, **kw) -> session.TestOptions:
    widths = dict(WIDTHS)
    program = dict(PROGRAM)
    if c.config.get("pixelfly"):
        widths["pixelfly"] = {**c.config["pixelfly"], "sparse_block": BLOCK, "attn_block": BLOCK}
        program.update(sparse_block=BLOCK, attn_block=BLOCK)
    else:
        program.update(attn_block=BLOCK)
    e = c.mix.engine
    per_seq = e["max_len"] // BLOCK
    # three quarters of the worst case: admission budgets lifetimes
    n_pages = max(per_seq + 1, (e["max_slots"] * per_seq + 1) * 3 // 4)
    return session.TestOptions(widths=widths, program=program, n_pages=n_pages, **kw)


def replace_mix(c: harness.Cell, **kw) -> harness.Cell:
    return dataclasses.replace(c, mix=dataclasses.replace(c.mix, **kw))
