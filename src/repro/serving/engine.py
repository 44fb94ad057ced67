"""Batched serving engine — continuous-batching-lite over slot-based caches.

A fixed decode batch of B slots; each slot holds one request's KV/recurrent
cache region.  Finished slots are refilled from the queue by running a
prefill for the new prompt and writing its cache into the slot (dynamic
batch-index update).  The decode loop is one jitted `decode_step` for the
whole batch every iteration — the standard TPU serving shape.

Resilience (DESIGN.md §14): the engine never dies because one request
does.  A crashing prefill is retried, then requeued, then isolated as a
poison request; a crashing decode step is retried and, when it keeps
failing, the most recently admitted request is evicted as the likely
poison; a step-count deadline bounds the whole run.  ``run`` returns the
requests (back-compat) and records a structured :class:`ServeReport` in
``last_report``.

Tracing: ``run`` writes host spans into the profiler's trace
(``jax.profiler.TraceAnnotation``, on the device planes' clock), and the
three programs are jitted as ``serve_prefill``, ``serve_slot_write`` and
``serve_decode``, so a trace shows ``jit_serve_prefill(...)``,
``jit_serve_slot_write(...)`` and ``jit_serve_decode(...)`` modules.  The
slot write donates the batch cache, so an admission updates it in place
with one launch.
Spans, and the kwargs each carries:

  serve.step         one loop iteration: fill, decode, hand-over  step
  serve.admit        one admission, whole                 uid, slot, tokens
  serve.prefill      the call into the prefill program (enqueue)  tokens
  serve.slot_write   the call into the slot-write program  leaves, donated
  serve.first_token  the first token's wait and its scatter
  serve.decode       the call into the decode program (enqueue)   active
  serve.decode_sync  argmax and the wait for the step's tokens
  serve.emit         the per-slot hand-over and retirement        done

A shared-prefix admission has no ``serve.prefill``.  With no profiler
running a span costs about a microsecond; its kwargs are encoded only
while a trace is active.

The straggler/deadline story for multi-host serving (and the ragged
dispatch notes) live in DESIGN.md §5; this single-host engine is what the
serve example + tests drive.
"""
from __future__ import annotations

import dataclasses
import json
import time
import warnings
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from ..core.resilience.faults import fault_point
from ..models import transformer as T
from ..models.config import ArchConfig


# --------------------------------------------------------------------------
# Shape buckets (DESIGN.md §15).  A live fleet must NEVER enter the lowering
# pipeline mid-traffic, so decode kernels are keyed by power-of-two
# (batch_slots, kv_len) buckets: every kv length inside a bucket resolves
# the same artifact-cache entry, and a warm-up pass over the bucket ladder
# covers steady state exactly.
# --------------------------------------------------------------------------

KV_BUCKET_FLOOR = 16        # smallest kv bucket (f32 lane-tile friendly)


def pow2_bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor)."""
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


def decode_bucket(batch_slots: int, kv_len: int) -> Tuple[int, int]:
    """The (batch_slots, kv_len) power-of-two bucket a decode step lands
    in.  kv floors at :data:`KV_BUCKET_FLOOR` so short caches do not churn
    tiny one-off kernels."""
    return (pow2_bucket(batch_slots),
            pow2_bucket(kv_len, floor=KV_BUCKET_FLOOR))


def kv_bucket_ladder(max_len: int) -> List[int]:
    """Every kv bucket a cache of capacity ``max_len`` can reach."""
    out, kv = [], KV_BUCKET_FLOOR
    while True:
        out.append(kv)
        if kv >= max_len:
            return out
        kv *= 2


class DecodeFastPath:
    """Bucketed fused decode-attention resolution (DESIGN.md §15).

    The decode-step extraction dedupes onto the flash_attention chain, so
    each (batch_slots, kv_len) bucket maps to one
    :func:`repro.bench.tasks.decode_fused_task` resolved through the
    degradation ladder (PR 7) and memoized: a warmed fleet serves every
    bucket from the artifact cache (``cached_tuned`` rung, zero
    lowering-pipeline entries) and an unwarmed one pays one generation
    per bucket, never per step.  Resolution failures are the CALLER's
    problem to contain — ``ServeEngine`` wraps the lookup so a fastpath
    fault can never break the decode loop (the ``serve.decode_fastpath``
    hook point injects exactly that).
    """

    def __init__(self, cfg: ArchConfig, cache=None, resolver=None,
                 quarantine=None, kv_dtype: str = "f32"):
        from ..core.resilience import (GuardedResolver, PersistentQuarantine,
                                       Quarantine)
        from ..core.tuning.cache import ArtifactCache
        self.cfg = cfg
        self.group = cfg.n_heads // cfg.n_kv_heads
        self.head_dim = cfg.resolved_head_dim
        # storage-dtype axis for the decode chain (DESIGN.md §17): every
        # bucket this instance resolves is keyed by it (task name + pinned
        # axes enter the cache fingerprint).  A dtype the chain's structure
        # does not admit (flash_attention today: both matmuls make every
        # tensor contraction-adjacent) clamps to f32 with a warning rather
        # than failing each bucket down the degradation ladder.
        self.requested_kv_dtype = str(kv_dtype or "f32")
        self.kv_dtype = self.requested_kv_dtype
        if self.kv_dtype != "f32":
            from ..core.fusion.chain import chain_storage_dtypes
            if self.kv_dtype not in chain_storage_dtypes("flash_attention"):
                warnings.warn(
                    f"kv_dtype '{self.kv_dtype}' is not admissible for the "
                    f"decode attention chain (quantization eligibility, "
                    f"DESIGN.md §17); serving buckets fall back to f32")
                self.kv_dtype = "f32"
        cache_obj = ArtifactCache.resolve(cache) if cache is not None \
            else None
        if resolver is None:
            if quarantine is None:
                # the quarantine table persists NEXT TO the cache it guards
                quarantine = (PersistentQuarantine.from_cache(cache_obj)
                              if cache_obj is not None else Quarantine())
            resolver = GuardedResolver(cache=cache_obj, tune=False,
                                       verify=False, quarantine=quarantine)
        self.resolver = resolver
        self._memo: Dict[Tuple[int, int], Any] = {}
        self.hits = 0
        self.misses = 0
        self.events: List[Any] = []

    def resolve(self, batch_slots: int, kv_len: int):
        """The ladder Resolution serving this step's bucket."""
        bucket = decode_bucket(batch_slots, kv_len)
        hit = bucket in self._memo
        dtag = "" if self.kv_dtype == "f32" else f":{self.kv_dtype}"
        fault_point("serve.decode_fastpath",
                    token=f"bucket={bucket[0]}x{bucket[1]}{dtag}:"
                          f"{'hit' if hit else 'miss'}")
        if hit:
            self.hits += 1
            return self._memo[bucket]
        from ..bench.tasks import decode_fused_task
        self.misses += 1
        task = decode_fused_task(self.group, self.head_dim, bucket[1],
                                 batch_slots=bucket[0],
                                 kv_dtype=self.kv_dtype)
        res = self.resolver.resolve(task)
        self.events.extend(res.events)
        self._memo[bucket] = res
        return res

    def warm(self, buckets) -> List[Any]:
        return [self.resolve(bs, kv) for bs, kv in buckets]

    @property
    def buckets(self) -> List[Tuple[int, int]]:
        return sorted(self._memo)


def warm_kernel_cache(cache=True, tasks=None, verify: bool = True,
                      tune: bool = False, tune_budget: int = 8,
                      guard=None, decode_buckets=None,
                      cfg: Optional[ArchConfig] = None,
                      manifest_path=None, kv_dtype: str = "f32") -> Dict:
    """Pre-populate the persistent artifact cache (DESIGN.md §8) with the
    framework hot-spot kernels (rmsnorm/softmax/adamw/swiglu/add_rmsnorm +
    mHC) so serving-time kernel (re)generation skips the lowering pipeline.

    Run once at deployment (or pass ``warm_kernels=True`` to ServeEngine);
    every later ``planner.generate`` against the same cache is a hit.
    ``verify`` defaults to True so warmed entries carry a Pass@1 verdict and
    satisfy later ``generate(verify=True)`` calls (unverified entries would
    be re-verified, defeating the warm-up).

    The warm-up SURVIVES partial failures (DESIGN.md §14): a kernel whose
    generation throws becomes an ``{"error": ...}`` row instead of killing
    the whole warm-up, and every row carries an ok/degraded/quarantined/
    error verdict.  Pass ``guard=True`` (or a configured
    :class:`~repro.core.resilience.GuardedResolver`) to resolve each
    kernel down the degradation ladder instead of failing it on the first
    generation error.  Returns a report dict with per-kernel outcomes,
    verdict counts, and cache stats.

    ``decode_buckets`` + ``cfg`` extend the warm-up over the decode fast
    path (DESIGN.md §15): each (batch_slots, kv_len) pair is canonicalized
    to its power-of-two bucket and warmed as a
    :func:`repro.bench.tasks.decode_fused_task`, so a fleet's
    steady-state decode resolves every bucket from cache.
    ``manifest_path`` publishes the warm-up as a JSON manifest another
    fleet member replays with :func:`warm_from_manifest`."""
    from ..core.generate import framework_tasks
    from ..core.planner import generate
    from ..core.resilience import GuardedResolver
    from ..core.tuning.cache import ArtifactCache
    cache_obj = ArtifactCache.resolve(cache)
    if cache_obj is None:
        raise ValueError("warm_kernel_cache needs a cache to warm; got "
                         f"cache={cache!r} (resolved to 'caching off')")
    resolver = None
    if guard is True:
        resolver = GuardedResolver(cache=cache_obj, tune=tune,
                                   tune_budget=tune_budget, verify=verify)
    elif guard:
        resolver = guard
    task_list = list(tasks if tasks is not None else framework_tasks())
    decode_info = None
    if decode_buckets:
        if cfg is None:
            raise ValueError("decode_buckets needs cfg for the attention "
                             "geometry (group / head_dim)")
        from ..bench.tasks import decode_fused_task
        group = cfg.n_heads // cfg.n_kv_heads
        head_dim = cfg.resolved_head_dim
        buckets = sorted({decode_bucket(bs, kv)
                          for bs, kv in decode_buckets})
        kv_dtype = str(kv_dtype or "f32")
        if kv_dtype != "f32":
            # same admissibility clamp as DecodeFastPath: warming an
            # inadmissible dtype would fail every bucket down the ladder
            from ..core.fusion.chain import chain_storage_dtypes
            if kv_dtype not in chain_storage_dtypes("flash_attention"):
                warnings.warn(
                    f"kv_dtype '{kv_dtype}' is not admissible for the "
                    f"decode attention chain; warming f32 buckets instead")
                kv_dtype = "f32"
        task_list += [decode_fused_task(group, head_dim, kv, batch_slots=bs,
                                        kv_dtype=kv_dtype)
                      for bs, kv in buckets]
        decode_info = {"group": int(group), "head_dim": int(head_dim),
                       "buckets": [list(b) for b in buckets],
                       "kv_dtype": kv_dtype}
    kernels = []
    for task in task_list:
        if resolver is not None:
            res = resolver.resolve(task)
            r = res.result
            kernels.append({
                "name": task.name,
                "comp_ok": bool(r.comp_ok) if r is not None else None,
                "pass_ok": (r.pass_ok if verify else None)
                           if r is not None else None,
                "error": r.error if r is not None else "",
                "from_cache": bool(r.cached) if r is not None else False,
                "rung": res.rung, "verdict": res.verdict,
                "degradations": [ev.describe() for ev in res.events]})
            continue
        try:
            r = generate(task, verify=verify, cache=cache_obj,
                         tune=tune, tune_budget=tune_budget)
        except Exception as e:  # noqa: BLE001 — isolate, record, continue
            kernels.append({"name": task.name, "comp_ok": False,
                            "pass_ok": None, "from_cache": False,
                            "error": f"{type(e).__name__}: {e}",
                            "verdict": "error"})
            continue
        ok = r.comp_ok and (r.pass_ok or not verify)
        kernels.append({"name": task.name, "comp_ok": r.comp_ok,
                        "pass_ok": r.pass_ok if verify else None,
                        "error": r.error, "from_cache": r.cached,
                        "verdict": "ok" if ok else "error"})
    verdicts: Dict[str, int] = {}
    for row in kernels:
        verdicts[row["verdict"]] = verdicts.get(row["verdict"], 0) + 1
    report = {"kernels": kernels, "verdicts": verdicts,
              **cache_obj.stats()}
    if decode_info is not None:
        report["decode"] = decode_info
    if manifest_path is not None:
        manifest = {"version": 1,
                    "kernels": [row["name"] for row in kernels],
                    "verdicts": verdicts}
        if decode_info is not None:
            manifest["decode"] = decode_info
        p = Path(manifest_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        tmp = p.with_name(p.name + ".tmp")
        tmp.write_text(json.dumps(manifest, indent=1, sort_keys=True))
        tmp.replace(p)
        report["manifest_path"] = str(p)
    return report


def load_warmup_manifest(path) -> Dict:
    """Read a warm-up manifest published by :func:`warm_kernel_cache`."""
    data = json.loads(Path(path).read_text())
    if data.get("version") != 1:
        raise ValueError(f"unsupported warm-up manifest version "
                         f"{data.get('version')!r} in {path}")
    return data


def warm_from_manifest(path, cache=True, verify: bool = True,
                       guard=None) -> Dict:
    """Replay a published warm-up manifest into ``cache`` — the fleet
    member side of the publishable warm-up (DESIGN.md §15): one member
    warms and publishes, every other member replays the manifest so its
    steady-state decode never enters the lowering pipeline.  Framework
    kernels are matched by name (manifest rows naming kernels this build
    no longer ships are skipped); decode buckets regenerate from the
    recorded (group, head_dim, buckets) geometry."""
    from ..core.generate import framework_tasks
    from ..bench.tasks import decode_fused_task
    manifest = load_warmup_manifest(path)
    names = set(manifest.get("kernels", ()))
    task_list = [t for t in framework_tasks() if t.name in names]
    dec = manifest.get("decode")
    if dec:
        task_list += [decode_fused_task(dec["group"], dec["head_dim"],
                                        int(kv), batch_slots=int(bs))
                      for bs, kv in dec["buckets"]]
    return warm_kernel_cache(cache, tasks=task_list, verify=verify,
                             guard=guard)


@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    generated: List[int] = field(default_factory=list)
    done: bool = False
    error: str = ""               # set when the engine isolated the request


@dataclass
class ServeReport:
    """Structured outcome of one ``ServeEngine.run`` (DESIGN.md §14)."""
    completed: List[int] = field(default_factory=list)      # uids
    failed: List[Dict[str, Any]] = field(default_factory=list)
    decode_steps: int = 0
    admit_retries: int = 0
    requeues: int = 0
    decode_retries: int = 0
    deadline_hit: bool = False
    prefill_shared: int = 0         # admissions served from a shared prefix
    prefill_memo_evictions: int = 0  # LRU evictions from the prefix memo
    fastpath_errors: int = 0        # contained fastpath-resolution failures
    slot_writes: int = 0            # prefilled caches written into a slot
    slot_writes_donated: int = 0    # ... that updated the batch cache in place
    slot_refill_s: List[float] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.failed and not self.deadline_hit


def _opaque(x) -> bool:
    """A cache leaf that the slot write leaves alone: an ``int`` or a
    ``None``."""
    return x is None or isinstance(x, int)


class ServeEngine:
    def __init__(self, params, cfg: ArchConfig, batch_slots: int,
                 max_len: int, greedy: bool = True,
                 warm_kernels: bool = False, kernel_cache=None,
                 decode_fastpath=True, prefix_sharing: bool = True,
                 prefix_memo_slots: int = 8, clock=None,
                 kv_dtype: str = "f32"):
        self.params = params
        self.cfg = cfg
        self.B = batch_slots
        self.max_len = max_len
        self.greedy = greedy
        # injectable wall clock (FaultClock in tests/bench sims): drives
        # wall-clock deadlines and slot-refill latency accounting
        self.clock = clock if clock is not None else time.monotonic
        # optional setup-time kernel warm-up: populate the artifact cache
        # (framework kernels + THIS engine's decode bucket ladder) so any
        # on-demand kernel resolution during serving is a cache hit
        # instead of a full transcompile (DESIGN.md §8, §15)
        self.kernel_warmup = None
        if warm_kernels:
            self.kernel_warmup = warm_kernel_cache(
                True if kernel_cache is None else kernel_cache,
                decode_buckets=[(batch_slots, kv)
                                for kv in kv_bucket_ladder(max_len)]
                if decode_fastpath else None,
                cfg=cfg if decode_fastpath else None,
                kv_dtype=kv_dtype)
        # the bucketed fused decode-attention fast path; pass a configured
        # DecodeFastPath to share one across engines, False to disable
        if isinstance(decode_fastpath, DecodeFastPath):
            self.fastpath: Optional[DecodeFastPath] = decode_fastpath
        elif decode_fastpath:
            self.fastpath = DecodeFastPath(cfg, cache=kernel_cache,
                                           kv_dtype=kv_dtype)
        else:
            self.fastpath = None
        self.prefix_sharing = bool(prefix_sharing)
        # LRU cap on memoized prefills (each entry holds a full
        # per-request KV cache, so an unbounded per-run memo scales with
        # the number of DISTINCT duplicated prompts — PR 8's memo did)
        self.prefix_memo_slots = max(0, int(prefix_memo_slots))
        self._prefix_counts: Dict[bytes, int] = {}
        self._prefix_memo: "OrderedDict[bytes, Tuple[Any, Any]]" = \
            OrderedDict()
        self.caches = T.init_caches(cfg, batch_slots, max_len)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_remaining = np.zeros(batch_slots, np.int64)
        # per-slot KV length (prompt + generated so far): drives the
        # decode-bucket lookup each step
        self.slot_len = np.zeros(batch_slots, np.int64)
        self._slot_freed_at: List[Optional[float]] = [None] * batch_slots
        # admission order tick per slot: poison isolation evicts the most
        # recently admitted request when the batched decode keeps crashing
        self.slot_admitted_at = np.zeros(batch_slots, np.int64)
        self._admit_tick = 0
        self.last_token = jnp.zeros((batch_slots, 1), jnp.int32)
        self.last_report: Optional[ServeReport] = None

        # named, so that their programs are named in a profiler trace
        def serve_decode(p, t, c):
            return T.decode_step(p, cfg, t, c)

        def serve_prefill(p, b):
            return T.prefill(p, cfg, b, max_len)

        def serve_slot_write(c_all, c_one, slot):
            # each leaf is (B, ...) <- (1, ...) or (repeats, B, ...) <-
            # (repeats, 1, ...): the slot's axis is the one whose size
            # differs (none when B == 1, and then slot is 0)
            def write(a, o):
                axis = next((i for i, (n, m) in enumerate(zip(a.shape,
                                                              o.shape))
                             if n != m), 0)
                start = [0] * a.ndim
                start[axis] = slot
                return jax.lax.dynamic_update_slice(a, o.astype(a.dtype),
                                                    start)
            return [write(a, o) for a, o in zip(c_all, c_one)]

        self._decode = jax.jit(serve_decode)
        self._prefill = jax.jit(serve_prefill)
        # the batch cache is donated: the write is in place.  The
        # one-request cache is not: the prefix memo reuses it.
        self._slot_write = jax.jit(serve_slot_write, donate_argnums=0)

    # ------------------------------------------------------------------
    def _admit(self, req: Request, slot: int) -> bool:
        """Prefill `req` (batch of 1) and write its cache into `slot`.

        Returns True when the request RETIRED AT ADMISSION — its
        prefill-produced first token already hit ``eos_id`` (or its token
        budget is a single token), so it must not occupy the slot for a
        decode step it does not need.

        Prefix sharing (DESIGN.md §15): when several queued requests
        carry the SAME prompt (N samples per prompt), the shared prefix
        is prefilled ONCE — later admissions broadcast the memoized
        first-token logits and per-request cache into their slot.  The
        memo is lazy AND bounded: only prompts with multiplicity > 1 are
        retained, an entry is dropped after its last sample admits, and
        at most ``prefix_memo_slots`` fingerprints stay resident (LRU —
        an evicted prompt's next admission simply re-prefills).  Greedy
        decode is bit-identical with sharing on or off and across
        evictions (the jitted prefill is deterministic, so the broadcast
        IS the recompute)."""
        fault_point("serve.admit", token=f"uid={req.uid}")
        rep = self.last_report
        key = (np.asarray(req.prompt, np.int32).tobytes()
               if self.prefix_sharing else None)
        left = 0
        if key is not None:
            # queued samples of this prompt remaining AFTER this one
            left = self._prefix_counts.get(key, 1) - 1
            self._prefix_counts[key] = left
        shared = self._prefix_memo.get(key) if key is not None else None
        if shared is not None:
            logits_last, caches1 = shared
            if left <= 0:
                self._prefix_memo.pop(key, None)   # last sample admitted
            else:
                self._prefix_memo.move_to_end(key)  # LRU touch
            if rep is not None:
                rep.prefill_shared += 1
        else:
            batch = {"tokens": jnp.asarray(req.prompt[None], jnp.int32)}
            with span("serve.prefill", tokens=len(req.prompt)):
                logits, caches1 = self._prefill(self.params, batch)
            logits_last = logits[0, -1]
            if key is not None and left > 0:
                self._prefix_memo[key] = (logits_last, caches1)
                while len(self._prefix_memo) > self.prefix_memo_slots:
                    self._prefix_memo.popitem(last=False)
                    if rep is not None:
                        rep.prefill_memo_evictions += 1

        # slot write: one program over the array leaves; a recurrent
        # cache's int and None leaves stay as they are
        flat, tree = jax.tree.flatten(self.caches, is_leaf=_opaque)
        ones = jax.tree.leaves(caches1, is_leaf=_opaque)
        idx = [i for i, o in enumerate(ones) if not _opaque(o)]
        with span("serve.slot_write", leaves=len(idx)) as sw:
            new = self._slot_write([flat[i] for i in idx],
                                   [ones[i] for i in idx], np.int32(slot))
            donated = int(bool(idx) and flat[idx[0]].is_deleted())
            sw.set_metadata(donated=donated)
        for i, a in zip(idx, new):
            flat[i] = a
        self.caches = jax.tree.unflatten(tree, flat)
        if rep is not None:
            rep.slot_writes += 1
            rep.slot_writes_donated += donated
        with span("serve.first_token"):
            nxt = int(jnp.argmax(logits_last))
            req.generated.append(nxt)
            if req.max_new_tokens <= 1 or (
                    req.eos_id is not None and nxt == req.eos_id):
                # first token is the last: retire now, leave the slot free
                req.done = True
                return True
            self.last_token = self.last_token.at[slot, 0].set(nxt)
        self.slot_req[slot] = req
        self.slot_remaining[slot] = req.max_new_tokens - 1
        self.slot_len[slot] = len(req.prompt)
        self._admit_tick += 1
        self.slot_admitted_at[slot] = self._admit_tick
        freed = self._slot_freed_at[slot]
        if freed is not None and rep is not None:
            rep.slot_refill_s.append(max(0.0, self.clock() - freed))
        self._slot_freed_at[slot] = None
        return False

    def _retire(self, slot: int):
        req = self.slot_req[slot]
        if req is not None:
            req.done = True
        self.slot_req[slot] = None
        self.slot_remaining[slot] = 0
        self.slot_len[slot] = 0
        self._slot_freed_at[slot] = self.clock()

    def _fail_request(self, req: Request, phase: str, error: str,
                      report: ServeReport):
        req.done = True
        req.error = error
        report.failed.append({"uid": req.uid, "phase": phase,
                              "error": error})

    def _evict_newest(self, error: str, report: ServeReport) -> bool:
        """Poison isolation for a persistently crashing decode step: the
        most recently admitted request is the likely trigger — fail it,
        free its slot, and let the batch continue."""
        active = [b for b in range(self.B) if self.slot_req[b] is not None]
        if not active:
            return False
        b = max(active, key=lambda i: self.slot_admitted_at[i])
        req = self.slot_req[b]
        self._fail_request(req, "decode", error, report)
        self.slot_req[b] = None
        self.slot_remaining[b] = 0
        self.slot_len[b] = 0
        self._slot_freed_at[b] = self.clock()
        return True

    def _deadline_fail(self, queue, reason: str, report: ServeReport):
        """Shared deadline failure path (step budget or wall clock): fail
        whatever is still in flight or waiting, but RETURN — a wedged
        decode must not hang the fleet."""
        report.deadline_hit = True
        for b in range(self.B):
            req = self.slot_req[b]
            if req is not None:
                self._fail_request(req, "deadline", reason, report)
                self.slot_req[b] = None
                self.slot_remaining[b] = 0
                self.slot_len[b] = 0
        while queue:
            self._fail_request(queue.popleft(), "deadline",
                               f"{reason} before admission", report)

    # ------------------------------------------------------------------
    def run(self, requests: List[Request], *, admit_retries: int = 1,
            decode_retries: int = 1, max_steps: Optional[int] = None,
            deadline_s: Optional[float] = None) -> List[Request]:
        """Serve ``requests`` to completion.  Per-request failures are
        retried (``admit_retries`` extra admission attempts, with the
        request requeued behind the waiting queue between attempts;
        ``decode_retries`` extra batched-step attempts before poison
        isolation evicts the most recently admitted request), and
        ``max_steps`` (default: a generous bound from the requests' token
        budgets) deadlines the whole run so it can never spin forever.
        ``deadline_s`` adds a WALL-CLOCK deadline on top of the step
        budget, measured on the engine's injectable ``clock`` so tests
        drive it deterministically via the fault harness.  Returns the
        requests; ``self.last_report`` carries the structured
        :class:`ServeReport`."""
        report = ServeReport()
        self.last_report = report
        queue = deque(requests)
        admit_attempts: Dict[int, int] = {}
        if max_steps is None:
            max_steps = 2 * sum(max(1, r.max_new_tokens)
                                for r in requests) + 8 * max(1, self.B)
        t_run = self.clock()
        # prefix sharing: prompt multiplicity across THIS run's requests
        # decides which prefills are worth memoizing (lazy broadcast)
        self._prefix_counts = {}
        self._prefix_memo = OrderedDict()
        if self.prefix_sharing:
            for r in requests:
                k = np.asarray(r.prompt, np.int32).tobytes()
                self._prefix_counts[k] = self._prefix_counts.get(k, 0) + 1
        # empty slots start "freed" now, so first admissions count as
        # refills against the run start
        for b in range(self.B):
            if self.slot_req[b] is None:
                self._slot_freed_at[b] = t_run
        active = lambda: any(r is not None for r in self.slot_req)  # noqa
        while queue or active():
            with span("serve.step", step=report.decode_steps):
                if deadline_s is not None and \
                        self.clock() - t_run >= deadline_s:
                    self._deadline_fail(
                        queue, f"wall-clock deadline {deadline_s:g}s "
                               f"exhausted", report)
                    break
                # fill free slots (admission failures retry, then isolate)
                for b in range(self.B):
                    while self.slot_req[b] is None and queue:
                        req = queue.popleft()
                        try:
                            with span("serve.admit", uid=req.uid, slot=b,
                                      tokens=len(req.prompt)):
                                retired = self._admit(req, b)
                        except Exception as e:  # noqa: BLE001 — isolate
                            n = admit_attempts.get(req.uid, 0) + 1
                            admit_attempts[req.uid] = n
                            err = f"{type(e).__name__}: {e}"
                            if n <= admit_retries:
                                report.admit_retries += 1
                                report.requeues += 1
                                queue.append(req)   # retry behind the queue
                            else:
                                self._fail_request(req, "admit", err, report)
                            continue
                        if retired:                 # EOS at admission
                            report.completed.append(req.uid)
                            continue
                        break                       # slot occupied
                if not active():
                    if queue:
                        continue    # everything admitted so far failed/EOSed
                    break
                # resolve this step's fused decode kernel through the
                # bucketed fast path (DESIGN.md §15).  Warmed: a pure cache
                # materialize.  Any resolution failure is CONTAINED — the
                # jitted decode step below must never be broken by the
                # fastpath.
                if self.fastpath is not None:
                    occupied = [b for b in range(self.B)
                                if self.slot_req[b] is not None]
                    kv = min(int(self.slot_len[occupied].max()) + 1,
                             self.max_len)
                    try:
                        self.fastpath.resolve(self.B, kv)
                    except Exception:  # noqa: BLE001 — isolate the fastpath
                        report.fastpath_errors += 1
                # one batched decode step (retried; then poison isolation)
                step_err = None
                n_active = sum(r is not None for r in self.slot_req)
                for attempt in range(decode_retries + 1):
                    try:
                        fault_point("serve.decode",
                                    token=f"step={report.decode_steps}")
                        with span("serve.decode", active=n_active):
                            logits, caches = self._decode(self.params,
                                                          self.last_token,
                                                          self.caches)
                        step_err = None
                        break
                    except Exception as e:  # noqa: BLE001
                        step_err = f"{type(e).__name__}: {e}"
                        if attempt < decode_retries:
                            report.decode_retries += 1
                if step_err is not None:
                    # decode keeps crashing: evict the newest admission and
                    # try again next loop — the engine survives, the poison
                    # request is reported
                    if not self._evict_newest(step_err, report):
                        break
                    continue
                self.caches = caches
                report.decode_steps += 1
                with span("serve.decode_sync"):
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                    self.last_token = nxt[:, None]
                    nxt_host = np.asarray(nxt)
                with span("serve.emit") as emit:
                    n_done = len(report.completed)
                    for b in range(self.B):
                        req = self.slot_req[b]
                        if req is None:
                            continue
                        tok = int(nxt_host[b])
                        req.generated.append(tok)
                        self.slot_remaining[b] -= 1
                        self.slot_len[b] += 1
                        if self.slot_remaining[b] <= 0 or (
                                req.eos_id is not None
                                and tok == req.eos_id):
                            report.completed.append(req.uid)
                            self._retire(b)
                    emit.set_metadata(done=len(report.completed) - n_done)
                if report.decode_steps >= max_steps:
                    self._deadline_fail(
                        queue, f"step budget {max_steps} exhausted", report)
                    break
        self._prefix_memo = OrderedDict()
        self._prefix_counts = {}
        return requests
