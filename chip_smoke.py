"""Chip smoke: drive the system's main paths once on a TPU, in one process.

    python chip_smoke.py [--seed N]     # one chip: generate, then serve
    python chip_smoke.py --chips 4      # four chips: sharded training only

One chip.  (a) Device: platform, kind and count.  (b) Generate: the
DSL -> transcompile -> Pallas pipeline (``planner.generate``, verify on)
for one task of each Table-1 category plus the fused ``add_rmsnorm`` and
``rmsnorm_swiglu`` chains; each checked kernel is compiled by Mosaic and
run on the chip, and must pass.  (c) Serve: ``internlm2-1.8b`` at its
published widths (24L, d=2048, 16H/8KV, d_ff=8192, vocab 92544, bf16
weights from ``--seed``, int8 KV cache) in a ``ServeEngine`` with 4 slots
and max_len 2048; 8 requests with 64-1024-token prompts each generate 16
tokens.  Prefill runs the generated flash-attention chain.  The shortest
request is replayed through the engine's prefill and decode programs, and
their logits and the engine's greedy tokens are checked against a float32
forward of the same weights at ``highest`` matmul precision.

Four chips.  ``internlm2-1.8b`` train steps at published widths through
``launch/train.py``'s sharded step (``make_sharded_train_step``) on a
(data=2, model=2) mesh, then a 2-layer copy stepped on one device and on
the mesh from the same seed and batches; their losses and gradient norms
must agree.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.
"""
import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# one task per Table-1 category (bench/tasks.py), each of which compiles
# for the chip at its check shapes
GENERATE_TASKS = ("relu", "exp", "cosine_sim_loss", "rmsnorm", "adamw",
                  "reduce_sum", "global_avg_pool")
FUSED_TASKS = ("add_rmsnorm", "rmsnorm_swiglu")

ARCH = "internlm2-1.8b"
SLOTS, MAX_LEN, N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 2048, 8, (64, 1024), 16
# bf16 weights and activations (int8 KV cache in decode) against a float32
# forward: max |logit difference| over every position and the whole
# vocabulary.  Each bound lies between the sound reading on the chip and
# the smallest reading with a planted fault (PERF.md, section 2).
PREFILL_TOL = 0.25
DECODE_TOL = 0.2
# a served token's reference logit below the reference maximum: at most
# twice the larger bound when the engine's logits are within it
TOKEN_GAP_TOL = 2 * max(PREFILL_TOL, DECODE_TOL)

# four chips: full-width train steps at this batch and sequence length; no
# warmup, so every step after the first runs on updated params
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 512
TRAIN_LR = 1e-3
# 2-layer copy, one device against the 2x2 mesh, per step: |loss diff| and
# |grad-norm diff| / grad norm.  Set like the serving bounds; a mesh that
# skips the update moves the loss by only 5e-3 at this learning rate.
TRAIN_LOSS_TOL = 1.5e-3
TRAIN_GNORM_TOL = 1e-3


def phase(name):
    print(f"== {name}", flush=True)
    return time.perf_counter()


def generate_phase():
    import jax
    from repro.bench.tasks import build_fused_suite, suite
    from repro.core.planner import default_inputs, generate

    t0 = phase("generate")
    by_name = {t.name: t for t in suite()}
    fused = {t.name: t for t in build_fused_suite()}
    runs = [(by_name[n], False) for n in GENERATE_TASKS]
    runs += [(fused[n], True) for n in FUSED_TASKS]
    failed = []
    for task, tune in runs:
        res = generate(task, verify=True, tune=tune)
        mosaic = False
        if res.check_artifact is not None:
            inputs = default_inputs(task, task.check_shapes)
            arrays = [inputs[tp.name] for tp in task.input_specs]
            text = jax.jit(res.check_artifact.entry).lower(*arrays) \
                .compile().as_text()
            mosaic = "tpu_custom_call" in text
        variant = res.tune.best.candidate.variant if res.tune else "default"
        print(f"  {task.category:13s} {task.name:16s} variant={variant:8s} "
              f"Comp@1={int(res.comp_ok)} Pass@1={int(res.pass_ok)} "
              f"max_rel_err={res.max_abs_err:.3g} "
              f"mosaic_kernel={'yes' if mosaic else 'no'}"
              + (f"  error: {res.error}" if res.error else ""), flush=True)
        if not (res.comp_ok and res.pass_ok and mosaic):
            failed.append(task.name)
    print(f"  generate: {len(runs) - len(failed)}/{len(runs)} kernels "
          f"compiled on the chip and passed "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    if failed:
        raise RuntimeError(f"generate phase failed for {failed}")


def count_eqns(jaxpr, name):
    """Equations of primitive ``name`` in ``jaxpr`` and the jaxprs nested
    in its equations (a loop body counts once)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    n += count_eqns(sub, name)
    return n


def reference_logits(params, cfg, tokens):
    """Float32 forward of the dense GQA transformer, written from its
    definition rather than from ``models/``: RMSNorm (eps 1e-6), rotary
    embedding on split halves, causal grouped-query attention, SwiGLU MLP,
    untied LM head.  ``tokens``: (S,) int32 -> logits (S, vocab)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    S = tokens.shape[0]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-6) * w

    inv = cfg.rope_theta ** (-jnp.arange(0, hd, 2, dtype=f32) / hd)
    ang = jnp.arange(S, dtype=f32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):                                       # (S, heads, hd)
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        a = p["block"]
        h = rms(x, p["norm1"]["scale"])
        q = rope((h @ a["wq"]).reshape(S, H, hd))
        k = jnp.repeat(rope((h @ a["wk"]).reshape(S, KV, hd)), H // KV, 1)
        v = jnp.repeat((h @ a["wv"]).reshape(S, KV, hd), H // KV, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(S, H * hd) @ a["wo"]
        m = p["ffn"]
        h = rms(x, p["norm2"]["scale"])
        g = h @ m["w_gate"]
        return x + (g * jax.nn.sigmoid(g) * (h @ m["w_up"])) @ m["w_down"], \
            None

    x = params["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, params["body"]["l0"])
    return rms(x, params["final_norm"]["scale"]) \
        @ params["lm_head"].astype(f32)


def check_outputs(prefill, decode, params, cfg, prompt, generated):
    """Replay one served request through the engine's ``prefill`` and
    ``decode`` programs at batch 1, and compare with ``reference_logits``
    over prompt + generated tokens (teacher-forced).  Returns max |logit
    difference| over the prompt (prefill) and over the decode steps,
    how many served tokens are the reference argmax, and the largest gap
    between a served token's reference logit and the reference maximum."""
    import jax
    import jax.numpy as jnp
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompt[None])})
    rows = [logits[0]]
    for t in generated[:-1]:
        step, caches = decode(params, jnp.full((1, 1), t, jnp.int32), caches)
        rows.append(step[0])
    got = np.asarray(jnp.concatenate(rows), np.float32)
    seq = jnp.asarray(np.concatenate([prompt, generated[:-1]]), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda p, t: reference_logits(p, cfg, t))(params, seq))
    S = len(prompt)
    diff = np.abs(got - want)
    pred = want[S - 1:]                  # row i predicts generated[i]
    toks = np.asarray(generated)
    return {
        "prefill": float(diff[:S].max()),
        "decode": float(diff[S:].max()),
        "ref_max": float(np.abs(want).max()),
        "exact": int(np.sum(pred.argmax(-1) == toks)),
        "gap": float(np.max(pred.max(-1) - pred[np.arange(len(toks)), toks])),
    }


def serve_phase(seed):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import Request, ServeEngine

    t0 = phase("serve")
    cfg = get_config(ARCH)
    params = jax.jit(lambda: T.init_params(jax.random.PRNGKey(seed), cfg))()
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"  {ARCH}: {cfg.n_layers}L d={cfg.d_model} "
          f"{cfg.n_heads}H/{cfg.n_kv_heads}KV d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} params={n_params} dtype={cfg.dtype} "
          f"kv_cache={cfg.kv_cache_dtype}", flush=True)
    engine = ServeEngine(params, cfg, batch_slots=SLOTS, max_len=MAX_LEN,
                         decode_fastpath=False)
    rng = np.random.RandomState(seed)
    lens = rng.randint(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, n)
                    .astype(np.int32), max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(lens)]
    t_run = time.perf_counter()
    engine.run(reqs)
    t_run = time.perf_counter() - t_run
    rep = engine.last_report
    for r in reqs:
        print(f"  req {r.uid}: prompt={len(r.prompt)} "
              f"generated={len(r.generated)}"
              + (f" FAILED: {r.error}" if r.error else ""), flush=True)
    print(f"  report: ok={rep.ok} completed={len(rep.completed)}/"
          f"{N_REQUESTS} decode_steps={rep.decode_steps} "
          f"run_wall_s={t_run:.1f} (host clock, compiles included)",
          flush=True)
    if not (rep.ok and len(rep.completed) == N_REQUESTS
            and all(len(r.generated) == NEW_TOKENS for r in reqs)):
        raise RuntimeError("serve phase: not every request completed")

    # which attention prefill ran: the generated chain is one pallas_call
    # per (layer, head) in the traced program, and the compiled program
    # holds its Mosaic kernel
    probe = reqs[int(np.argmin(lens))]
    batch = {"tokens": jnp.asarray(probe.prompt[None])}
    n_calls = count_eqns(jax.make_jaxpr(engine._prefill)(params, batch).jaxpr,
                         "pallas_call")
    mosaic = "tpu_custom_call" in \
        engine._prefill.lower(params, batch).compile().as_text()
    print(f"  prefill attention: impl={cfg.attn_impl} on "
          f"{jax.default_backend()}, {n_calls} generated flash chain calls "
          f"({cfg.n_layers} layers x {cfg.n_heads} heads), Mosaic kernel in "
          f"the compiled program: {'yes' if mosaic else 'no'}", flush=True)
    if not (n_calls == cfg.n_layers * cfg.n_heads and mosaic):
        raise RuntimeError("prefill did not run the generated flash chain")

    r = check_outputs(engine._prefill, engine._decode, params, cfg,
                      probe.prompt, probe.generated)
    n = len(probe.generated)
    print(f"  replay of req {probe.uid} vs f32 reference "
          f"(max|ref| {r['ref_max']:.4g}): prefill logits over "
          f"{len(probe.prompt)} tokens max|diff|={r['prefill']:.4g} "
          f"(tol {PREFILL_TOL}); decode logits over {n - 1} steps "
          f"max|diff|={r['decode']:.4g} (tol {DECODE_TOL})", flush=True)
    print(f"  greedy tokens vs teacher-forced f32 reference: {r['exact']}/{n}"
          f" exact argmax, largest gap to the reference max {r['gap']:.4g} "
          f"(tol {TOKEN_GAP_TOL})", flush=True)
    if not (r["prefill"] <= PREFILL_TOL and r["decode"] <= DECODE_TOL
            and r["gap"] <= TOKEN_GAP_TOL):
        raise RuntimeError("serve phase: outputs outside tolerance")
    print(f"  serve: done ({time.perf_counter() - t0:.1f}s)", flush=True)


def train_run(cfg, mesh, seed, steps=TRAIN_STEPS):
    """Losses and gradient norms of ``steps`` sharded train steps on
    ``mesh``, params and batches from ``seed``."""
    import jax
    import jax.numpy as jnp
    from repro.data import DataConfig, SyntheticLM
    from repro.training import optimizer as opt
    from repro.training.train import init_sharded, make_sharded_train_step
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                                  global_batch=TRAIN_BATCH, seed=seed))
    batches = [{k: jnp.asarray(v) for k, v in data.batch(i).items()}
               for i in range(steps)]
    ocfg = opt.AdamWConfig(lr=TRAIN_LR, warmup_steps=0, total_steps=steps)
    step, (pshard, oshard, bshard) = make_sharded_train_step(
        cfg, ocfg, mesh, batches[0])
    params, state = init_sharded(cfg, pshard, oshard, seed)
    losses, gnorms = [], []
    for b in batches:
        params, state, m = step(params, state, jax.device_put(b, bshard))
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))
    return losses, gnorms


def train_phase(seed):
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh

    t0 = phase("train (4 chips)")
    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh4 = make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devs[:1])

    cfg = get_config(ARCH)
    losses, gnorms = train_run(cfg, mesh4, seed)
    print(f"  {ARCH} published widths on a (data=2, model=2) mesh, batch "
          f"{TRAIN_BATCH} x {TRAIN_SEQ}: losses {losses}, grad norms "
          f"{gnorms}", flush=True)
    if not all(math.isfinite(x) for x in losses + gnorms):
        raise RuntimeError("train phase: non-finite loss or grad norm")
    # the sharded step runs XLA attention (a Mosaic kernel cannot be
    # partitioned); the one-device copy runs the same attention, so the
    # comparison isolates the sharding
    cfg2 = cfg.scaled(n_layers=2, attn_impl="xla")
    (l1, g1), (l4, g4) = train_run(cfg2, mesh1, seed), \
        train_run(cfg2, mesh4, seed)
    dloss = max(abs(a - b) for a, b in zip(l1, l4))
    dgnorm = max(abs(a - b) / a for a, b in zip(g1, g4))
    print(f"  2-layer copy, one device vs 2x2 mesh: losses {l1} vs {l4}, "
          f"max|diff|={dloss:.4g} (tol {TRAIN_LOSS_TOL}); grad norms {g1} "
          f"vs {g4}, max relative diff={dgnorm:.4g} (tol {TRAIN_GNORM_TOL})",
          flush=True)
    if not (dloss <= TRAIN_LOSS_TOL and dgnorm <= TRAIN_GNORM_TOL):
        raise RuntimeError("train phase: mesh and one-device steps differ")
    print(f"  train: done ({time.perf_counter() - t0:.1f}s)", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    phase("device")
    print(f"  platform={dev.platform} device_kind={dev.device_kind} "
          f"count={n_dev}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.chips == 4:
        train_phase(args.seed)
    else:
        generate_phase()
        serve_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
