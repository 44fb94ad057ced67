"""Serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --requests 4 [--no-smoke --max-len 2048 --prompt-len 64 1024]

``--smoke`` (the default) serves the architecture's reduced config;
``--no-smoke`` serves it at its published widths.  Prompt lengths are
uniform in ``--prompt-len MIN MAX``.  The process
exits 1 unless every request completed and no fast-path resolution failed.

Single-host slot engine on the container; the decode step is the same unit
the dry-run lowers against the production mesh (launch/steps.py).

The decode fast path (DESIGN.md §15) is on by default: per-step fused
decode-attention kernels resolve by power-of-two (batch_slots, kv_len)
bucket.  Fleet warm-up options:

* ``--warm --cache DIR`` warms the artifact cache (framework kernels +
  this engine's decode bucket ladder) before serving, so steady-state
  decode never enters the lowering pipeline;
* ``--publish-manifest PATH`` additionally publishes the warm-up as a
  JSON manifest;
* ``--warm-manifest PATH`` replays a published manifest into the cache
  instead of warming from scratch (the other-fleet-member side).

``--profile DIR`` writes a profiler trace of the serving run into DIR
(``DIR/plugins/profile/<time>/*.xplane.pb``): the engine's ``serve.*``
spans, the ``jit_serve_prefill``/``jit_serve_decode`` programs and, on a
TPU, each device operation with its layer scope.  Off by default.
"""
import argparse
import contextlib
import sys

import jax
import numpy as np

from ..configs import get_config
from ..models import transformer as T
from ..serving import (Request, ServeEngine, kv_bucket_ladder,
                       warm_from_manifest, warm_kernel_cache)
from .compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default) or published widths")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(8, 8),
                    metavar=("MIN", "MAX"),
                    help="prompt lengths, uniform in [MIN, MAX]")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="wall-clock deadline for the whole run")
    ap.add_argument("--no-fastpath", action="store_true",
                    help="disable the bucketed fused decode fast path")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=("f32", "int8", "fp8"),
                    help="storage-dtype axis for the decode buckets "
                         "(DESIGN.md §17); a dtype the decode chain does "
                         "not admit falls back to f32 with a warning")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable shared-prefix admission")
    ap.add_argument("--cache", default=None,
                    help="artifact cache dir for decode kernels "
                         "(default: caching off)")
    ap.add_argument("--warm", action="store_true",
                    help="warm the kernel cache (framework + decode "
                         "buckets) before serving; needs --cache")
    ap.add_argument("--publish-manifest", default=None,
                    help="with --warm: publish the warm-up manifest here")
    ap.add_argument("--warm-manifest", default=None,
                    help="replay a published warm-up manifest into the "
                         "cache instead of warming from scratch")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a profiler trace of the serving run here")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache = args.cache if args.cache else None
    if args.warm_manifest:
        rep = warm_from_manifest(args.warm_manifest,
                                 cache=cache if cache else True)
        print(f"warmed from manifest {args.warm_manifest}: "
              f"{rep['verdicts']}")
    engine = ServeEngine(params, cfg, batch_slots=args.slots,
                         max_len=args.max_len,
                         warm_kernels=args.warm, kernel_cache=cache,
                         decode_fastpath=not args.no_fastpath,
                         prefix_sharing=not args.no_prefix_sharing,
                         kv_dtype=args.kv_dtype)
    if args.warm and engine.kernel_warmup is not None:
        print(f"warm-up: {engine.kernel_warmup['verdicts']}")
        if args.publish_manifest:
            # re-resolving the warmed kernels is all cache hits; this call
            # only exists to write the manifest
            warm_kernel_cache(
                True if cache is None else cache,
                decode_buckets=[(args.slots, kv)
                                for kv in kv_bucket_ladder(args.max_len)],
                cfg=cfg, manifest_path=args.publish_manifest,
                kv_dtype=args.kv_dtype)
            print(f"published manifest -> {args.publish_manifest}")
    rng = np.random.RandomState(0)
    lo, hi = args.prompt_len
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab,
                                              rng.randint(lo, hi + 1))
                    .astype(np.int32), max_new_tokens=args.max_new)
            for i in range(args.requests)]
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        engine.run(reqs, deadline_s=args.deadline_s)
    for r in reqs:
        tag = f"  [FAILED: {r.error}]" if r.error else ""
        print(f"req {r.uid}: {r.generated}{tag}")
    rep = engine.last_report
    print(f"report: ok={rep.ok} completed={len(rep.completed)} "
          f"failed={len(rep.failed)} steps={rep.decode_steps} "
          f"requeues={rep.requeues} deadline_hit={rep.deadline_hit} "
          f"prefill_shared={rep.prefill_shared} "
          f"fastpath_errors={rep.fastpath_errors}")
    if engine.fastpath is not None:
        print(f"fastpath: buckets={engine.fastpath.buckets} "
              f"kv_dtype={engine.fastpath.kv_dtype} "
              f"hits={engine.fastpath.hits} "
              f"misses={engine.fastpath.misses}")
    return 0 if rep.ok and rep.fastpath_errors == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
