"""The driver of mixes of kind ``serve_closed_loop``: a closed-loop serving
cell's set-up, its timed window and its output check.

The timed path is one ``ServeEngine.run`` call, the call users make, with
enough requests from the seed to outlast the window.  Set-up makes the
weights on the device, builds the engine, and serves one short request
per prompt length of the mix through the same ``run``, so that every
program the window uses is compiled or loaded before it opens.  The cell's
own file (``cells/<cell>.json``) gives its clients, one per engine slot.
The configuration's architecture module (``archs/<bench_arch>.py``) gives
the program's model, the weights and the reference.
"""
from __future__ import annotations

import math
import time
from functools import partial

import numpy as np

from chipbench.lib import reference, traffic
from chipbench.lib.timeline import Timeline, WindowClock, window_stats

# a program lowered inside the process: compiled, or loaded from the cache
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Programs lowered after ``start()``, from a ``jax.monitoring``
    listener registered for the life of the process."""

    def __init__(self):
        import jax
        self.started = None
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == LOWERING_EVENT and self.started is not None:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def add_spans(engine):
    """Host spans around the engine's calls, written into the profiler's
    trace by ``TraceAnnotation``: ``cb.admit`` around an admission,
    ``cb.prefill`` and ``cb.decode`` around the program calls."""
    import jax
    ann = jax.profiler.TraceAnnotation
    prefill, decode, admit = engine._prefill, engine._decode, engine._admit

    def traced_prefill(params, batch):
        with ann("cb.prefill", tokens=int(batch["tokens"].shape[1])):
            return prefill(params, batch)

    def traced_decode(*args):
        with ann("cb.decode"):
            return decode(*args)

    def traced_admit(req, slot):
        with ann("cb.admit"):
            return admit(req, slot)

    engine._prefill, engine._decode, engine._admit = \
        traced_prefill, traced_decode, traced_admit


class Profiler:
    """The profiler over the first ``seconds`` of the window."""

    def __init__(self, log_dir, seconds: float):
        self.log_dir, self.seconds = log_dir, seconds
        self.t0 = self.t1 = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()

    def maybe_stop(self):
        if self.t0 is not None and time.perf_counter() - self.t0 >= \
                self.seconds:
            self.stop()


def sample_for_check(reqs, completed: set, seed: int, want_tokens: int):
    """Finished requests to compare with the reference, drawn from the
    seed: the longest (prompt and output) first, then others until about
    ``want_tokens`` served tokens are in the sample."""
    done = [r for r in reqs if r.uid in completed]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.generated),
                                       -r.uid))
    rest = [r for r in done if r is not longest]
    order = traffic.rng_for(seed, "check").permutation(len(rest))
    out, n = [longest], len(longest.generated)
    for i in order:
        if n >= want_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].generated)
    return out


def run(conf: dict, mix: dict, own: dict, seed: int, seconds: float,
        arch, trace_dir=None, fault=None, t_start: float | None = None,
        controls=()) -> dict:
    """One run of a serving cell whose model is ``arch``'s.  ``trace_dir``
    turns the profiler on over the window's first ``mix['trace_seconds']``.
    ``fault`` (tests only) is called with the engine before the window, to
    break the timed path.  ``controls`` ("int8", "fp8") also reads those
    controls' gaps on the same sample (``calibrate.py``, tests).  Returns
    the run's record for the metric readers."""
    import jax
    from repro.serving import Request, ServeEngine

    t_start = time.perf_counter() if t_start is None else t_start
    counter = CompileCounter()
    cfg = arch.arch_config(conf)
    max_len = traffic.max_len(mix)
    B = int(own["clients"])
    params = jax.jit(partial(arch.make_params, conf))(traffic.jax_key(seed))
    jax.block_until_ready(params)

    clock = WindowClock()
    engine = ServeEngine(params, cfg, batch_slots=B, max_len=max_len,
                         decode_fastpath=False, clock=clock)
    warm = [Request(uid=r.uid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens)
            for r in traffic.warmup_requests(mix, conf["vocab_size"])]
    engine.run(warm)
    if not engine.last_report.ok:
        raise RuntimeError(f"warm-up failed: {engine.last_report.failed}")

    n = B + math.ceil(seconds * mix["queue_per_s"])
    specs = traffic.requests(mix, seed, conf["vocab_size"], n)
    profiler = Profiler(trace_dir, mix["trace_seconds"]) \
        if trace_dir is not None else None
    timeline = Timeline(B, clock, on_open=profiler.start if profiler
                        else None)
    reqs = [Request(uid=s.uid, prompt=s.prompt,
                    max_new_tokens=s.max_new_tokens,
                    generated=timeline.list_for(s.max_new_tokens))
            for s in specs]
    if profiler is not None:
        add_spans(engine)
        decode = engine._decode

        def decode_then_check(*args):
            profiler.maybe_stop()
            return decode(*args)
        engine._decode = decode_then_check
    if fault is not None:
        fault(engine)
    counter.started = time.perf_counter()
    engine.run(reqs, deadline_s=seconds)
    if profiler is not None:
        profiler.stop()
    rep = engine.last_report
    if not rep.deadline_hit:
        raise RuntimeError(f"the queue of {n} requests ran out before the "
                           "window closed: raise queue_per_s in the mix")
    rec = timeline.record()
    uid_index = {r.uid: i for i, r in enumerate(reqs)}
    rec.failed = {uid_index[f["uid"]] for f in rep.failed
                  if f["phase"] != "deadline"}
    stats = window_stats(rec, seconds)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # the program's state goes before the reference runs
    completed = set(rep.completed)
    engine.caches = engine.last_token = None
    del engine

    sample = sample_for_check(reqs, completed, seed, mix["check_tokens"])
    gaps = {q: [] for q in (None, *controls)}
    for r in sample:
        g, c = reference.served_gaps(
            arch, params, conf, np.asarray(r.prompt),
            np.asarray(list(r.generated)), max_len, mix["output"]["max"],
            controls)
        for q, gq in ((None, g), *c.items()):
            gaps[q].append(gq)
    gaps = {q: np.concatenate(v) for q, v in gaps.items() if v}
    # nothing finished: nothing passes
    gap = {q: float(gaps[q].max()) if sample else math.inf
           for q in (None, *controls)}
    t0 = rec.opened_at
    return {
        "arch": arch, "conf": conf, "setup_s": t0 - t_start,
        "window": (t0, t0 + seconds), "stats": stats, "record": rec,
        "prompt_lens": [len(s.prompt) for s in specs],
        "compiles_in_window": counter.between(t0, t0 + seconds),
        "memory_peak_bytes": peak,
        "checked_requests": len(sample),
        "checked": sum(len(r.generated) for r in sample),
        "checks": {"token_gap": gap[None]},
        "controls": {q: {"token_gap": gap[q]} for q in controls},
        # for calibrate.py: how the gaps behind each maximum are spread
        "gap_stats": {q or "program": gap_stats(g) for q, g in gaps.items()},
    }


def gap_stats(g: np.ndarray) -> dict:
    """Tokens compared, how many are not the reference's first, and the
    mean, 99th percentile and largest gap."""
    return {"tokens": int(g.size), "off_top": int((g > 0).sum()),
            "mean": float(g.mean()), "p99": float(np.percentile(g, 99)),
            "max": float(g.max())}
