"""Serving engine + performance-model sanity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import (DecodeFastPath, Request, ServeEngine,
                           ServeReport, decode_bucket, kv_bucket_ladder,
                           load_warmup_manifest, pow2_bucket,
                           warm_from_manifest, warm_kernel_cache)


@pytest.fixture(scope="module")
def env():
    cfg = get_config("internlm2-1.8b", smoke=True)
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def test_serve_engine_continuous_batching():
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=64)
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, 8)
                    .astype(np.int32), max_new_tokens=5) for i in range(5)]
    done = eng.run(reqs)
    assert all(r.done for r in done)
    assert all(len(r.generated) == 5 for r in done)


def test_serve_matches_unbatched_decode():
    """Tokens generated through the slot engine == direct greedy decode."""
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab, 8).astype(np.int32)

    # direct decode
    logits, caches = T.prefill(params, cfg, {"tokens": jnp.asarray(
        prompt[None])}, max_len=32)
    toks = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(4):
        lg, caches = T.decode_step(params, cfg,
                                   jnp.asarray([[toks[-1]]], jnp.int32),
                                   caches)
        toks.append(int(jnp.argmax(lg[0, 0])))

    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32)
    req = Request(uid=0, prompt=prompt, max_new_tokens=5)
    eng.run([req])
    assert req.generated == toks


def test_eos_at_admission_retires_without_decoding():
    """A request whose prefill-produced FIRST token already hits eos_id
    (or whose budget is a single token) must retire at admission — not
    occupy a slot and decode a full extra step (regression: the old engine
    always decoded once, yielding 2 tokens for max_new_tokens=1)."""
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab, 8).astype(np.int32)

    probe = Request(uid=0, prompt=prompt, max_new_tokens=1)
    eng.run([probe])
    assert probe.done and len(probe.generated) == 1
    assert eng.last_report.decode_steps == 0
    assert eng.last_report.completed == [0]

    # same prompt, generous budget, eos = the known first token: the EOS
    # match at admission must retire it identically
    req = Request(uid=1, prompt=prompt, max_new_tokens=5,
                  eos_id=probe.generated[0])
    eng.run([req])
    assert req.done and req.generated == probe.generated
    assert eng.last_report.decode_steps == 0
    assert eng.last_report.ok and eng.last_report.completed == [1]


def test_serve_report_on_clean_run():
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=64)
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, 8)
                    .astype(np.int32), max_new_tokens=3) for i in range(3)]
    eng.run(reqs)
    rep = eng.last_report
    assert rep.ok and not rep.failed and not rep.deadline_hit
    assert sorted(rep.completed) == [0, 1, 2]
    assert rep.requeues == 0 and rep.decode_retries == 0


# ---------------------------------------------------------------------------
# Decode fast path: shape buckets, warm cache, zero-lowering steady state
# (DESIGN.md §15)
# ---------------------------------------------------------------------------

def test_pow2_bucket_and_ladder():
    assert pow2_bucket(1) == 1 and pow2_bucket(3) == 4
    assert pow2_bucket(16, floor=16) == 16
    assert pow2_bucket(17, floor=16) == 32
    assert decode_bucket(2, 16) == (2, 16)
    assert decode_bucket(2, 17) == (2, 32)       # edge+1 crosses the bucket
    assert decode_bucket(3, 5) == (4, 16)        # kv floors at 16
    assert kv_bucket_ladder(64) == [16, 32, 64]
    assert kv_bucket_ladder(100) == [16, 32, 64, 128]


class _StubResolver:
    """Records resolved tasks without entering the lowering pipeline."""

    def __init__(self):
        self.tasks = []

    def resolve(self, task):
        from repro.core.resilience import Resolution
        self.tasks.append(task)
        return Resolution(task.name, f"fp:{task.name}", "cached_tuned",
                          None, (), runner=lambda *a: None)


def test_bucket_boundary_keys_and_memo(env):
    """kv at a bucket edge vs edge+1 resolve DISTINCT tasks (distinct
    cache keys); every kv inside a bucket reuses the memoized resolution
    — no re-lower within a bucket."""
    from repro.core.tuning.cache import _digest, task_fingerprint
    cfg, _ = env
    stub = _StubResolver()
    fp = DecodeFastPath(cfg, resolver=stub)
    r_edge = fp.resolve(2, 32)
    r_over = fp.resolve(2, 33)
    assert [t.name for t in stub.tasks] == ["decode_attention_b2_kv32",
                                            "decode_attention_b2_kv64"]
    keys = {_digest(task_fingerprint(t)) for t in stub.tasks}
    assert len(keys) == 2                        # distinct cache keys
    assert r_edge is not r_over
    # within-bucket kv lengths: memo hit, resolver NOT re-entered
    assert fp.resolve(2, 20) is r_edge
    assert fp.resolve(2, 32) is r_edge
    assert fp.resolve(2, 40) is r_over
    assert len(stub.tasks) == 2
    assert fp.misses == 2 and fp.hits == 3
    assert fp.buckets == [(2, 32), (2, 64)]


def test_warmed_engine_steady_state_zero_lowering(env, tmp_path):
    """THE fleet guarantee: a warmed engine's steady-state decode never
    enters the lowering pipeline — PIPELINE_COUNTERS record zero
    transcompiles across the whole serve loop, every bucket lands on the
    cached_tuned rung, and zero degradation events fire."""
    from repro.core.lowering.pipeline import PIPELINE_COUNTERS
    from repro.core.resilience import drain_events
    from repro.core.tuning import ArtifactCache
    cfg, params = env
    cache = ArtifactCache(str(tmp_path))
    warm = warm_kernel_cache(
        cache, tasks=[],            # decode buckets only: keep the test lean
        decode_buckets=[(2, kv) for kv in kv_bucket_ladder(32)], cfg=cfg)
    assert warm["verdicts"] == {"ok": len(warm["kernels"])}
    drain_events()
    before = dict(PIPELINE_COUNTERS)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                      kernel_cache=cache)
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, 8)
                    .astype(np.int32), max_new_tokens=4) for i in range(3)]
    eng.run(reqs)
    rep = eng.last_report
    assert rep.ok and rep.decode_steps > 0
    assert dict(PIPELINE_COUNTERS) == before     # ZERO lowering entries
    assert rep.fastpath_errors == 0
    assert eng.fastpath.events == [] and drain_events() == []
    assert eng.fastpath.misses == len(eng.fastpath.buckets)
    assert eng.fastpath.hits == rep.decode_steps - eng.fastpath.misses
    for res in eng.fastpath._memo.values():
        assert res.rung == "cached_tuned" and res.result.cached


def test_warmup_manifest_round_trip(env, tmp_path):
    """One fleet member warms and PUBLISHES; another replays the manifest
    into its own cache and reaches the same warmed state."""
    from repro.core.tuning import ArtifactCache
    cfg, _ = env
    man = tmp_path / "warmup.json"
    warm_kernel_cache(ArtifactCache(str(tmp_path / "a")), tasks=[],
                      decode_buckets=[(2, 16), (2, 24)], cfg=cfg,
                      manifest_path=man)
    data = load_warmup_manifest(man)
    assert data["version"] == 1
    assert data["decode"]["buckets"] == [[2, 16], [2, 32]]  # canonicalized
    assert set(data["kernels"]) == {"decode_attention_b2_kv16",
                                    "decode_attention_b2_kv32"}
    rep = warm_from_manifest(man, cache=ArtifactCache(str(tmp_path / "b")))
    assert rep["verdicts"] == {"ok": 2}
    assert {k["name"] for k in rep["kernels"]} == set(data["kernels"])
    bad = tmp_path / "bad.json"
    bad.write_text('{"version": 99}')
    with pytest.raises(ValueError, match="manifest version"):
        load_warmup_manifest(bad)


def test_tokens_bit_identical_fastpath_on_off(env):
    """The fast path only changes kernel STAGING, never numerics: greedy
    tokens with the bucketed fast path (and prefix sharing) enabled are
    bit-identical to the plain unbucketed engine."""
    cfg, params = env
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab, 8).astype(np.int32)
               for _ in range(3)]

    def serve(**kw):
        eng = ServeEngine(params, cfg, batch_slots=2, max_len=32, **kw)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        assert eng.last_report.ok
        return [r.generated for r in reqs]

    plain = serve(decode_fastpath=False, prefix_sharing=False)
    stub = DecodeFastPath(cfg, resolver=_StubResolver())
    fast = serve(decode_fastpath=stub, prefix_sharing=True)
    assert fast == plain
    assert stub.misses >= 1                      # the fast path really ran


# ---------------------------------------------------------------------------
# Prefix sharing (N samples per prompt)
# ---------------------------------------------------------------------------

def test_prefix_sharing_prefills_once_per_distinct_prompt(env):
    cfg, params = env
    rng = np.random.RandomState(11)
    shared = rng.randint(0, cfg.vocab, 8).astype(np.int32)
    other = rng.randint(0, cfg.vocab, 8).astype(np.int32)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                      decode_fastpath=False)
    prefills = []
    orig = eng._prefill

    def prefill(p, b):
        prefills.append(orig(p, b))
        return prefills[-1]
    eng._prefill = prefill
    reqs = [Request(uid=i, prompt=shared.copy(), max_new_tokens=4)
            for i in range(3)]
    reqs.append(Request(uid=3, prompt=other, max_new_tokens=4))
    eng.run(reqs)
    rep = eng.last_report
    assert rep.ok and rep.prefill_shared == 2    # samples 2 and 3 broadcast
    assert len(prefills) == 2                    # one per DISTINCT prompt
    assert eng._prefix_memo == {}                # memo dropped after the run
    # the slot write donates the batch cache, never the memoized
    # one-request cache: it is still readable after the admissions
    memo = jax.tree.leaves(prefills[0][1])
    assert not any(a.is_deleted() for a in memo)
    assert all(np.isfinite(np.asarray(a, np.float32)).all() for a in memo)
    # greedy: every sample of the shared prompt generates the same tokens
    assert reqs[0].generated == reqs[1].generated == reqs[2].generated


def test_prefix_sharing_tokens_bit_identical_on_off(env):
    cfg, params = env
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, cfg.vocab, 8).astype(np.int32)

    def serve(sharing):
        eng = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                          decode_fastpath=False, prefix_sharing=sharing)
        reqs = [Request(uid=i, prompt=prompt.copy(), max_new_tokens=5)
                for i in range(3)]
        eng.run(reqs)
        return [r.generated for r in reqs]

    on, off = serve(True), serve(False)
    assert on == off


def test_prefix_memo_lru_cap_evicts_and_stays_bit_identical(env):
    """FIXED (PR 8 follow-up): the prefill memo was per-run and UNBOUNDED —
    every distinct duplicated prompt parked a full KV cache for the whole
    run.  It is now an LRU capped at ``prefix_memo_slots`` admitted-prompt
    fingerprints: overflow evicts the least-recently-used entry, an
    evicted prompt's next sample re-prefills, and greedy outputs stay
    bit-identical before/after eviction (and vs sharing off)."""
    cfg, params = env
    rng = np.random.RandomState(17)
    # 3 distinct prompts, 2 samples each, interleaved so a 1-slot memo
    # must evict between the two samples of every prompt
    prompts = [rng.randint(0, cfg.vocab, 8).astype(np.int32)
               for _ in range(3)]
    order = [0, 1, 2, 0, 1, 2]

    def serve(sharing, slots=1):
        eng = ServeEngine(params, cfg, batch_slots=1, max_len=32,
                          decode_fastpath=False, prefix_sharing=sharing,
                          prefix_memo_slots=slots)
        reqs = [Request(uid=i, prompt=prompts[k].copy(), max_new_tokens=4)
                for i, k in enumerate(order)]
        eng.run(reqs)
        return eng, [r.generated for r in reqs]

    eng1, capped = serve(True, slots=1)
    rep = eng1.last_report
    assert rep.ok
    assert rep.prefill_memo_evictions > 0       # the cap actually bit
    assert len(eng1._prefix_memo) == 0          # dropped after the run
    assert rep.prefill_shared < len(order) - len(prompts) + 1

    eng8, roomy = serve(True, slots=8)
    assert eng8.last_report.prefill_memo_evictions == 0
    # all second samples broadcast when the memo never overflows
    assert eng8.last_report.prefill_shared == 3

    _, off = serve(False)
    assert capped == roomy == off               # bit-identical throughout


# ---------------------------------------------------------------------------
# Admission: the slot write
# ---------------------------------------------------------------------------

def _opaque(x):
    return x is None or isinstance(x, int)


def _eager_slot_write(c_all, c_one, slot):
    """The per-leaf eager write an admission made before its write was one
    program: the slot's axis is 0, and 1 under the stacked ``body``
    (``(repeats, B, ...)``); int and None leaves pass through."""
    def leaf(path, a, o):
        if _opaque(o):
            return a
        axis = 1 if getattr(path[0], "key", None) == "body" else 0
        start = [0] * a.ndim
        start[axis] = slot
        return jax.lax.dynamic_update_slice(a, o.astype(a.dtype), start)
    return jax.tree_util.tree_map_with_path(leaf, c_all, c_one,
                                            is_leaf=_opaque)


@pytest.mark.parametrize("arch,unroll,opaque", [
    ("internlm2-1.8b", True, False),      # per-layer int8 KV + scales
    ("internlm2-1.8b", False, False),     # stacked (repeats, B, ...)
    ("xlstm-1.3b", True, True),           # mLSTM/sLSTM state + int leaves
], ids=["unrolled_int8", "stacked", "recurrent"])
def test_slot_write_one_donated_program_matches_eager(arch, unroll, opaque):
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              serve_unroll_layers=unroll)
    if arch == "internlm2-1.8b":
        assert cfg.kv_cache_dtype == "int8"
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    B, max_len = 3, 16

    def with_opaque(c):     # a recurrent step count and an absent state
        return ({**c, "prelude": [*c["prelude"], {"step": 5, "h": None}]}
                if opaque else c)

    eng = ServeEngine(params, cfg, batch_slots=B, max_len=max_len,
                      decode_fastpath=False)
    eng.caches = with_opaque(eng.caches)
    prefill, ones = eng._prefill, []

    def recorded_prefill(p, b):
        logits, c = prefill(p, b)
        ones.append(with_opaque(c))
        return logits, ones[-1]
    eng._prefill = recorded_prefill
    eng.last_report = rep = ServeReport()
    want = with_opaque(T.init_caches(cfg, B, max_len))
    rng = np.random.RandomState(23)
    for slot in (2, 0, 1):
        before = [a for a in jax.tree.leaves(eng.caches) if not _opaque(a)]
        prompt = rng.randint(0, cfg.vocab, 5 + slot).astype(np.int32)
        eng._admit(Request(uid=slot, prompt=prompt, max_new_tokens=4), slot)
        want = _eager_slot_write(want, ones[-1], slot)
        assert all(a.is_deleted() for a in before)   # updated in place
    got_l, got_t = jax.tree.flatten(eng.caches, is_leaf=_opaque)
    want_l, want_t = jax.tree.flatten(want, is_leaf=_opaque)
    assert got_t == want_t
    for g, w in zip(got_l, want_l):
        if _opaque(w):
            assert g is w or g == w
        else:
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # one program serves every slot
    assert eng._slot_write._cache_size() == 1
    assert rep.slot_writes == rep.slot_writes_donated == 3


# ---------------------------------------------------------------------------
# Tracing: the engine's serve.* host spans
# ---------------------------------------------------------------------------

SERVE_SPANS = {"serve.step", "serve.admit", "serve.prefill",
               "serve.slot_write", "serve.first_token", "serve.decode",
               "serve.decode_sync", "serve.emit"}


def _trace_events(log_dir):
    """(name, start, end, stats, line) of every event in the one trace
    under ``log_dir``."""
    import glob
    from jax.profiler import ProfileData
    [path] = glob.glob(str(log_dir / "plugins" / "profile" / "*" /
                           "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for k, ln in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats), (plane.name, k)) for e in ln.events]
    return out


def test_engine_spans_nest_and_leave_tokens_alone(env, tmp_path):
    cfg, params = env
    rng = np.random.RandomState(19)
    prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32)
               for n in (8, 12, 6)]

    def serve(traced):
        eng = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                          decode_fastpath=False)
        # the third request shares the first's prompt: no prefill of its own
        reqs = [Request(uid=10 + i, prompt=prompts[k].copy(),
                        max_new_tokens=3)
                for i, k in enumerate((0, 1, 0, 2))]
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(str(tmp_path), profiler_options=opts):
                eng.run(reqs)
        else:
            eng.run(reqs)
        return eng, [r.generated for r in reqs]

    _, plain = serve(False)
    eng, traced = serve(True)
    rep = eng.last_report
    assert traced == plain and rep.ok
    events = _trace_events(tmp_path)
    spans = [e for e in events if e[0].startswith("serve.")]
    assert {s[0] for s in spans} == SERVE_SPANS
    by = {n: [s for s in spans if s[0] == n] for n in SERVE_SPANS}

    def inside(child, parents):
        return [p for p in parents if p[4] == child[4]
                and p[1] <= child[1] and child[2] <= p[2]]

    for name, parent in (("serve.prefill", "serve.admit"),
                         ("serve.slot_write", "serve.admit"),
                         ("serve.first_token", "serve.admit"),
                         ("serve.admit", "serve.step"),
                         ("serve.decode", "serve.step"),
                         ("serve.decode_sync", "serve.step"),
                         ("serve.emit", "serve.step")):
        for s in by[name]:
            assert len(inside(s, by[parent])) == 1, (name, s)
    admits = by["serve.admit"]
    assert sorted(s[3]["uid"] for s in admits) == [10, 11, 12, 13]
    assert all({"slot", "tokens"} <= set(s[3]) for s in admits)
    assert len(by["serve.prefill"]) == 3 and rep.prefill_shared == 1
    assert len(by["serve.decode"]) == rep.decode_steps
    assert all(1 <= s[3]["active"] <= 2 for s in by["serve.decode"])
    assert all(s[3]["leaves"] > 0 for s in by["serve.slot_write"])
    # every write updated the batch cache in place, in its own program
    assert all(s[3]["donated"] == 1 for s in by["serve.slot_write"])
    assert rep.slot_writes == rep.slot_writes_donated == \
        len(by["serve.slot_write"]) == 4
    # ... with one launch each, of the program named jit_serve_slot_write
    calls = [e for e in events if e[0] == "PjitFunction(serve_slot_write)"]
    for s in by["serve.slot_write"]:
        mine = [c for c in calls if inside(c, [s])]
        # the first call nests its slow path in its fast one
        assert len([c for c in mine if len(inside(c, mine)) == 1]) == 1
    leaves = jax.tree.leaves(eng.caches)
    assert "module @jit_serve_slot_write" in eng._slot_write.lower(
        leaves, leaves, np.int32(0)).as_text()
    assert sum(s[3]["done"] for s in by["serve.emit"]) == \
        len(rep.completed)


def test_traffic_model_exact_for_relu():
    from repro.bench import suite
    from repro.bench.model import analyze_program, _padded_shapes_for
    from repro.core.planner import generate
    task = [t for t in suite() if t.name == "relu"][0]
    r = generate(task, verify=False)
    tr = analyze_program(r.artifact.program,
                         _padded_shapes_for(r.artifact.program, task.shapes))
    n = 1
    for s in task.shapes["input"]:
        n *= s
    # relu reads + writes each element exactly once (padding < 1%)
    assert tr.loaded >= 4 * n and tr.loaded < 4 * n * 1.01
    assert tr.stored >= 4 * n and tr.stored < 4 * n * 1.01


def test_fast_model_optimizer_fusion_win():
    from repro.bench import suite
    from repro.bench.model import fast_ratio
    from repro.core.planner import generate
    task = [t for t in suite() if t.name == "adamw"][0]
    r = generate(task, verify=False)
    ratio = fast_ratio(task, r.artifact.program)
    assert ratio > 1.5   # fused optimizer beats eager multi-kernel sequence


def test_collective_hlo_parser():
    from repro.launch.hlo_stats import collective_bytes
    hlo = """
      %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
      %ar.1 = f32[1024]{0} all-reduce(%y), to_apply=%add
      %cp = f32[4,4]{1,0} collective-permute(%z)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 4096
    assert out["total"] == 8 * 128 * 2 + 2 * 4096 + 64
