"""Data pipeline determinism + serving engine behavior."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, get_smoke_config
from repro.core.params import init_params
from repro.data.pipeline import PipelineConfig, Prefetcher, SyntheticStream, shard_batch
from repro.distributed.sharding import ShardCtx
from repro.models import api as mapi
from repro.serve.engine import Request, ServeEngine


def test_stream_deterministic_and_seekable():
    cfg = get_smoke_config("qwen3-0.6b")
    s1 = SyntheticStream(cfg, ShapeConfig("t", 16, 4, "train"))
    s2 = SyntheticStream(cfg, ShapeConfig("t", 16, 4, "train"))
    b_a = s1.batch_at(7)
    b_b = s2.batch_at(7)          # fresh object, same (seed, step)
    for k in b_a:
        np.testing.assert_array_equal(b_a[k], b_b[k])
    # different steps differ
    assert not np.array_equal(s1.batch_at(8)["tokens"], b_a["tokens"])
    # targets are next-token shifted view of the same underlying sequence
    assert b_a["targets"].shape == b_a["tokens"].shape


def test_stream_has_learnable_structure():
    cfg = get_smoke_config("qwen3-0.6b")
    s = SyntheticStream(cfg, ShapeConfig("t", 256, 2, "train"),
                        PipelineConfig(bigram_eps=0.25))
    b = s.batch_at(0)
    nxt = (b["tokens"] * s._a + s._c) % cfg.vocab_size
    frac = (nxt == b["targets"]).mean()
    assert frac > 0.6, frac        # ~75% deterministic bigram


def test_prefetcher_order_and_seek():
    cfg = get_smoke_config("gru-jet")
    s = SyntheticStream(cfg, ShapeConfig("t", cfg.gru.seq_len, 2, "train"))
    shardings = jax.tree_util.tree_map(lambda _: None, s.batch_at(0))
    pf = Prefetcher(s, shardings, start_step=3, depth=2)
    b3 = pf.next()
    np.testing.assert_allclose(np.asarray(b3["features"]),
                               s.batch_at(3)["features"])
    pf.seek(10)
    b10 = pf.next()
    np.testing.assert_allclose(np.asarray(b10["features"]),
                               s.batch_at(10)["features"])


def test_serve_engine_generates():
    cfg = get_smoke_config("qwen3-0.6b")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=3)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, size=6).astype(np.int32),
                    max_new_tokens=n) for n in (3, 5, 2)]
    done = engine.generate(reqs)
    assert [len(r.out) for r in done] == [3, 5, 2]
    assert all(r.done for r in done)
    stats = engine.latency_stats()
    assert stats["steps"] >= 1


def test_serve_engine_gru_wave_depth2():
    """Feature-vector wave serving through a depth-2 GRU stack: per-step
    decode latency is measured (the paper's figure of merit)."""
    from repro.configs.base import GRUConfig
    cfg = get_smoke_config("gru-jet").replace(
        gru=GRUConfig(input_dim=5, hidden_dim=16, num_classes=5, seq_len=20,
                      num_layers=2))
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=3)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.normal(size=(s, 5)).astype(np.float32),
                    max_new_tokens=n) for s, n in ((6, 3), (4, 5), (6, 2))]
    done = engine.generate(reqs)
    assert [len(r.out) for r in done] == [3, 5, 2]
    assert all(r.done for r in done)
    assert all(0 <= t < 5 for r in done for t in r.out)
    stats = engine.latency_stats()
    assert stats["steps"] >= 1
    # streamed decode features are honored
    engine2 = ServeEngine(cfg, params, ShardCtx(), max_batch=1)
    stream = rng.normal(size=(4, 5)).astype(np.float32)
    done2 = engine2.generate([Request(prompt=stream[:2], max_new_tokens=4,
                                      stream=stream)])
    assert len(done2[0].out) == 4


def test_serve_engine_gru_matches_model_api():
    """Engine prefill+decode == direct model-API calls (deep config)."""
    import jax.numpy as jnp
    cfg = get_smoke_config("gru-jet-deep")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(3, 5)).astype(np.float32)
    logits, cache = A.prefill(params, cfg, {"features": jnp.asarray(feats[None])},
                              ShardCtx())
    logits2, _ = A.decode_step(params, cfg, cache,
                               jnp.asarray(feats[-1][None]), ShardCtx())
    expect = int(np.argmax(np.asarray(logits2)[0]))
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=1)
    done = engine.generate([Request(prompt=feats, max_new_tokens=1)])
    assert done[0].out[0] == expect


def test_serve_engine_no_retrace_same_bucket():
    """Two GRU waves with DIFFERENT prompt lengths in the same power-of-two
    bucket share one wave-program entry (prefill bucket) and trace it
    exactly once; the decode step compiles once for the engine lifetime
    (fixed slots)."""
    cfg = get_smoke_config("gru-jet-deep")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2, bucket_min=8)
    rng = np.random.default_rng(0)

    def wave(S):
        return [Request(prompt=rng.normal(size=(S, 5)).astype(np.float32),
                        max_new_tokens=2) for _ in range(2)]

    engine.generate(wave(5))                     # warmup: bucket 8
    n_prefill = len(engine._wave_jit)
    n_decode = len(engine._decode_jit)
    traces = {k: f._cache_size() for k, f in engine._wave_jit.items()}
    engine.generate(wave(7))                     # different S, same bucket
    assert len(engine._wave_jit) == n_prefill == 1
    assert len(engine._decode_jit) == n_decode == 1
    for k, f in engine._wave_jit.items():
        assert f._cache_size() == traces[k] == 1, (k, f._cache_size())
    for f in engine._decode_jit.values():
        assert f._cache_size() == 1
    # a longer prompt opens exactly one NEW bucket
    engine.generate(wave(11))                    # bucket 16
    assert len(engine._wave_jit) == 2
    assert len(engine._decode_jit) == 1


def test_serve_engine_decode_cache_keyed_by_batch():
    """Regression: the decode jit cache is keyed by batch shape, so waves
    of different sizes get their own donated-cache jit instead of silently
    retracing one unkeyed entry."""
    cfg = get_smoke_config("qwen3-0.6b")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=3)
    rng = np.random.default_rng(0)

    def wave(B):
        return [Request(prompt=rng.integers(0, cfg.vocab_size, size=5)
                        .astype(np.int32), max_new_tokens=2)
                for _ in range(B)]

    engine.generate(wave(2))
    engine.generate(wave(1))
    assert set(engine._decode_jit) == {(2,), (1,)}
    for f in engine._decode_jit.values():
        assert f._cache_size() == 1              # each traced exactly once
    done = engine.generate(wave(2))              # reuses the (2,) entry
    assert engine._decode_jit[(2,)]._cache_size() == 1
    assert [len(r.out) for r in done] == [2, 2]


def test_serve_engine_gru_continuous_batching():
    """More requests than slots: finished streams retire mid-wave and
    queued requests are admitted into the freed slots — everyone is served
    with correct lengths and only ONE prefill bucket is compiled. Admits
    that land on the same step are BATCHED into one prefill."""
    cfg = get_smoke_config("gru-jet-deep")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2, bucket_min=8)
    rng = np.random.default_rng(0)
    budgets = [2, 5, 3, 4, 1]
    reqs = [Request(prompt=rng.normal(size=(3 + i % 4, 5)).astype(np.float32),
                    max_new_tokens=n) for i, n in enumerate(budgets)]
    done = engine.generate(reqs)
    assert [len(r.out) for r in done] == budgets
    assert all(r.done for r in done)
    assert all(0 <= t < 5 for r in done for t in r.out)
    # 5 requests through 2 slots: 1 cohort prefill + 1 single admit (req2
    # into req0's slot) + ONE batched admit (req1 and req2 finish on the
    # same step, so req3+req4 share a single prefill), all through the
    # SAME bucket jit (prompts 3..6 all bucket to 8)
    stats = engine.latency_stats()
    assert stats["prefills"] == 3
    assert len(engine._wave_jit) == 1
    for f in engine._wave_jit.values():
        assert f._cache_size() == 1
    # mid-wave admission really overlapped: total decode steps is less than
    # a serial 2-slot schedule would need (bounded by the longest lane sum)
    assert stats["steps"] >= max(budgets)


def test_serve_engine_decode_backend_attribution():
    """latency_stats attributes every recorded decode step to the backend
    that actually ran it: attribution is keyed by the decode jit the step
    ran under (frozen at that jit's trace time — the trace embeds the
    backend) instead of trusting a wave-start snapshot, and
    ``decode_backends`` stays aligned with ``step_times`` across
    continuous-batching admits."""
    cfg = get_smoke_config("gru-jet-deep")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2, bucket_min=8)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.normal(size=(3, 5)).astype(np.float32),
                    max_new_tokens=n) for n in (2, 5, 3, 4)]
    done = engine.generate(reqs)
    assert all(r.done for r in done)
    stats = engine.latency_stats()
    # one attribution per recorded step, consistent with the executor's
    # resolved decode backend for the (fixed-slot) batch shape
    assert len(engine.decode_backends) == stats["steps"]
    from repro.models import gru_lm
    expect = gru_lm.serve_executable(cfg, batch=2,
                                     mode="decode").decode_backend
    assert engine.decode_backend == expect
    assert set(engine.decode_backends) == {expect}
    assert stats["decode_backend_steps"] == {expect: stats["steps"]}


def test_serve_engine_gru_batched_admits():
    """When several slots free on the SAME decode step, the engine runs
    ONE bucketed prefill for all admitted requests (ROADMAP item): equal
    budgets retire the whole cohort at once, so 6 requests through 3
    slots cost exactly 2 prefills — and every request still gets the
    answer a solo engine gives it."""
    cfg = get_smoke_config("gru-jet-deep")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    rng = np.random.default_rng(3)
    prompts = [rng.normal(size=(3 + i % 3, 5)).astype(np.float32)
               for i in range(6)]
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=3, bucket_min=8)
    done = engine.generate([Request(prompt=p, max_new_tokens=2)
                            for p in prompts])
    assert engine.latency_stats()["prefills"] == 2     # cohort + ONE batched
    assert all(len(r.out) == 2 for r in done)
    # the batched-admit rows were scattered into the right slots: each
    # request's outputs match a single-request engine (one engine reused
    # across prompts — its jits are cached, so this stays cheap)
    solo = ServeEngine(cfg, params, ShardCtx(), max_batch=1, bucket_min=8)
    for p, r in zip(prompts, done):
        ref = solo.generate([Request(prompt=p, max_new_tokens=2)])[0]
        assert r.out == ref.out


def test_serve_engine_gru_bucketed_prefill_exact():
    """Bucket padding must not change results: a batch-1 engine answer
    equals the direct model-API answer on the UNPADDED prompt, even though
    the engine pads the prompt up to the bucket length (mask exactness)."""
    cfg = get_smoke_config("gru-jet-deep")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(3, 5)).astype(np.float32)   # S=3 -> bucket 8
    logits, cache = A.prefill(params, cfg,
                              {"features": jnp.asarray(feats[None])},
                              ShardCtx())
    logits2, _ = A.decode_step(params, cfg, cache,
                               jnp.asarray(feats[-1][None]), ShardCtx())
    expect = int(np.argmax(np.asarray(logits2)[0]))
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=1, bucket_min=8)
    done = engine.generate([Request(prompt=feats, max_new_tokens=1)])
    assert done[0].out[0] == expect


def test_serve_engine_gru_pallas_backend():
    """The fused decode path serves end-to-end (backend="pallas"): same
    class predictions as the XLA engine on the same wave."""
    import dataclasses
    cfg = get_smoke_config("gru-jet-deep")
    cfg_p = cfg.replace(gru=dataclasses.replace(cfg.gru, backend="pallas"))
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    rng = np.random.default_rng(2)
    prompts = [rng.normal(size=(4, 5)).astype(np.float32) for _ in range(2)]
    outs = []
    for c in (cfg, cfg_p):
        engine = ServeEngine(c, params, ShardCtx(), max_batch=2)
        # serving prep attaches the pre-stacked decode weights exactly once
        assert "stacked_cells" in engine.params
        done = engine.generate([Request(prompt=p, max_new_tokens=3)
                                for p in prompts])
        outs.append([r.out for r in done])
    assert outs[0] == outs[1]


def test_serve_engine_masked_prefill_runs_pallas():
    """Acceptance: a ServeEngine prefill with a NON-TRIVIAL length mask
    (ragged prompts inside one bucket) executes the fused Pallas sequence
    kernel — asserted via the executor plan the engine recorded, not
    inferred — and the masked, bucketed results equal the direct
    model-API answers on the UNPADDED prompts (mask exactness end to
    end)."""
    import dataclasses
    cfg = get_smoke_config("gru-jet-deep")
    cfg = cfg.replace(gru=dataclasses.replace(cfg.gru, backend="pallas"))
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    rng = np.random.default_rng(5)
    # ragged lengths 3 and 6 -> both left-padded into the 8-bucket: the
    # mask rows are genuinely non-trivial (and differ per row)
    prompts = [rng.normal(size=(s, 5)).astype(np.float32) for s in (3, 6)]
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2, bucket_min=8)
    done = engine.generate([Request(prompt=p, max_new_tokens=1)
                            for p in prompts])
    assert engine.prefill_backends == ["pallas_fused"], engine.prefill_backends
    assert engine.decode_backend == "pallas_fused"
    for p, r in zip(prompts, done):
        logits, cache = A.prefill(params, cfg,
                                  {"features": jnp.asarray(p[None])},
                                  ShardCtx())
        logits2, _ = A.decode_step(params, cfg, cache,
                                   jnp.asarray(p[-1][None]), ShardCtx())
        assert r.out[0] == int(np.argmax(np.asarray(logits2)[0]))


def test_serve_engine_greedy_matches_model():
    """Engine's first generated token == argmax of the model prefill."""
    cfg = get_smoke_config("qwen3-0.6b").replace(dtype="float32",
                                                 param_dtype="float32")
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), "float32")
    prompt = np.arange(5, dtype=np.int32)
    logits, _ = A.prefill(params, cfg, {"tokens": jnp.asarray(prompt[None])},
                          ShardCtx())
    expect = int(np.argmax(np.asarray(logits)[0]))
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=1)
    done = engine.generate([Request(prompt=prompt, max_new_tokens=1)])
    assert done[0].out[0] == expect
