"""A cell at the smoke size for the CPU tests: two layers at widths of
64, bf16 like the served configurations, a 96-block pool (a CPU reports
no memory to size one from), and a mix of short prompts whose few step
shapes compile in seconds."""

from __future__ import annotations

from .harness import Cell


def smoke_model(gqa: bool = False) -> dict:
    return {"n_layers": 2, "d_model": 64, "n_heads": 4,
            "n_kv_heads": 2 if gqa else 4, "head_dim": 16, "d_ff": 128,
            "vocab_size": 256, "mlp": "gelu_tanh" if gqa else "swiglu",
            "norm": "layernorm", "norm_eps": 1e-6, "rope_theta": 10000.0,
            "rotary_dim": 16, "dtype": "bfloat16"}


#: the smoke cell's pool, in blocks
SMOKE_BLOCKS = 96

#: the smoke cell's limit on the widest logit gap, set from CPU readings
#: of the program and of the fp8 control at this size (PERF.md, section 2)
SMOKE_GAP_LIMIT = 0.02


def smoke_config(gqa: bool = False) -> dict:
    return {"name": "smoke", "repo_arch": "starcoder2-3b" if gqa
            else "stablelm-3b", "model": smoke_model(gqa),
            "serve": {"block_size": 4, "max_batch": 4, "chunk_size": 8,
                      "scheme": "WFE"},
            "limits": {"max_logit_gap": SMOKE_GAP_LIMIT}}


def smoke_traffic(loop: str = "open", prefix: bool = False) -> dict:
    t = {"loop": loop, "rate_per_s": 6.0, "clients": 6,
         "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 20},
         "output": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                    "min": 2, "max": 10}}
    if prefix:
        t["prefix"] = {"contexts": 3, "length": 8, "zipf_s": 1.0}
    return t


#: the metrics a smoke cell reports: every end-to-end reader, and the
#: per-layer readers that need no device trace
SMOKE_END_TO_END = (("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
                    ("tpot_ms", "ms"), ("itl_p50_ms", "ms"),
                    ("tokens_per_s", "tokens/s"), ("setup_s", "s"))
SMOKE_PER_LAYER = (("sched.mixed_step_share.itl", "ratio"),
                   ("sched.prefix_hit_share.ttft", "ratio"),
                   ("sched.rows_per_step.tput", "tokens/step"),
                   ("pool.evictions_per_req.tput", "ratio"))


def smoke_cell(gqa: bool = False, loop: str = "open",
               prefix: bool = False) -> Cell:
    return Cell(f"smoke.{loop}", smoke_config(gqa),
                smoke_traffic(loop, prefix), 1,
                [{"name": n, "unit": u} for n, u in SMOKE_END_TO_END],
                [{"name": n, "unit": u} for n, u in SMOKE_PER_LAYER])
