"""Shape-bucket policy suite (the ISSUE-5 engine surface).

* ``maxlen`` (coarse, default) and ``pow2`` (legacy) buckets generate
  token-IDENTICAL output — padding a table wider never changes attention
  (dead slots are bounded out of the kernel walk / masked in the ref);
* the ``maxlen`` width covers the batch's final table width, so a
  request's bucket never changes across its lifetime;
* a growing-context serve run under ``maxlen`` compiles each jitted step
  for at most as many shapes as ``pow2`` does — the recompile win
  ``serve_bench --decode-heavy`` measures, asserted here at the
  per-shape compile-cache level (the CI gate's mechanism);
* invalid policies are rejected at construction.
"""

import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import ServeEngine

#: skewed on purpose: one long-generation request walks its table through
#: several pow2 boundaries while the short ones stay narrow
PROMPTS = [([3, 1, 4, 1, 5], 26), ([2, 7], 4), ([9, 2, 6], 5), ([8], 4)]


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("stablelm-3b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, params


def _serve(cfg, params, policy):
    engine = ServeEngine(cfg, params, n_blocks=48, block_size=2,
                         max_batch=4, chunk_size=4, bucket_policy=policy,
                         era_freq=4, cleanup_freq=4)
    tid = engine.pool.register_thread()
    reqs = [engine.submit(p, n) for p, n in PROMPTS]
    engine.run(tid)
    assert all(r.done for r in reqs)
    assert engine.pool.unreclaimed() == 0
    return engine, [list(r.generated) for r in reqs]


def test_coarse_and_pow2_buckets_token_identical(smoke_model):
    cfg, params = smoke_model
    _, coarse = _serve(cfg, params, "maxlen")
    _, pow2 = _serve(cfg, params, "pow2")
    assert coarse == pow2


def test_maxlen_width_covers_final_table(smoke_model):
    """The maxlen bucket is computed from prompt + max_new_tokens at
    admission: it must cover the deepest table any plan member ever
    grows, and stay one value for the request's whole lifetime."""
    cfg, params = smoke_model
    engine = ServeEngine(cfg, params, n_blocks=48, block_size=2,
                         max_batch=4, chunk_size=4, bucket_policy="maxlen")
    tid = engine.pool.register_thread()
    prompt, n_new = PROMPTS[0]
    req = engine.submit(prompt, n_new)
    final_blocks = -(-(len(prompt) + n_new) // 2)
    widths = set()
    plan = engine.sched.tick(tid)
    while plan is not None:
        tables, _ = engine._bucket_tables(plan, engine.max_batch)
        widths.add(tables.shape[1])
        assert tables.shape[1] >= final_blocks
        engine.execute_plan(plan, tid)
        plan = engine.sched.tick(tid)
    assert req.done
    assert len(widths) == 1  # ONE width bucket across prefill + decode
    engine.drain(tid)


def test_invalid_bucket_policy_rejected(smoke_model):
    cfg, params = smoke_model
    with pytest.raises(ValueError, match="bucket_policy"):
        ServeEngine(cfg, params, bucket_policy="hwm")


def test_maxlen_compiles_no_more_shapes_than_pow2(smoke_model):
    """The compile-count gate at test scale: serving the skewed workload
    from a cold cache, the coarse policy must touch at most as many
    compiled shapes as the pow2 ladder — and stay within the small
    absolute budget the scenario implies (one decode + one prefill shape
    per cold size class)."""
    cfg, params = smoke_model
    counts = {}
    for policy in ("maxlen", "pow2"):
        engine = ServeEngine(cfg, params, n_blocks=48, block_size=2,
                             max_batch=4, chunk_size=4,
                             bucket_policy=policy,
                             era_freq=4, cleanup_freq=4)
        # the jitted steps are lru-shared across engines over one config:
        # clear between policies so counts measure the policy, not order
        engine.clear_compile_caches()
        before = engine.compile_cache_size()
        tid = engine.pool.register_thread()
        for p, n in PROMPTS:
            engine.submit(p, n)
        engine.run(tid)
        counts[policy] = engine.compile_cache_size() - before
    assert counts["maxlen"] <= counts["pow2"]
    # the skew spans 2 width classes ({16-blk long, 4-blk shorts}) and 3
    # pow2 chunk-length buckets ({1, 2, 4} from the ragged prompts): at
    # most 1 decode shape (the long pins every batch) + 5 live (width,
    # chunk) prefill pairs.  pow2 additionally walks the decode ladder.
    assert counts["maxlen"] <= 6
