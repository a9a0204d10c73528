"""Run the serving path and the AgileNN offload path once on a TPU.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the sharded serving queue on a
                                    # (data 2, model 2) mesh of four chips

One chip: qwen2-0.5b at its published widths (random weights from
--seed) serves a 16-request mixed-length queue through `ServeEngine` and
the continuous scheduler, one equal-length batch through the engine's
fast path, and a short streaming-frontend pass with preemption and the
prefix cache on; first-token logits are checked against a float32
prefill of each prompt alone.  Then AgileNN's deployment forward runs at
the paper's 96x96 evaluation size and its fused offload kernel is
checked against the jnp path.

--chips 4 runs only the queue, once on the mesh and once on one chip of
the same process, and compares their first-token logits.

Everything runs in this one process, which holds the chip(s); it starts
no child.  Compiled programs persist in JAX_COMPILATION_CACHE_DIR, else
in .jax_cache/ at the repository root.  Progress and checks go to
earlier lines; the last line of standard output is the JSON verdict,
printed only when every phase passed.  A missing TPU, or a checkout
without the repository, exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs import get_config  # noqa: E402
from repro.configs.agilenn_cifar import AgileNNConfig  # noqa: E402
from repro.core.agile import agile_forward, init_agile_params  # noqa: E402
from repro.kernels.offload_fused.ops import (  # noqa: E402
    fused_offload, fused_offload_jnp, fused_offload_op)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.launch.serve import queue_workload  # noqa: E402
from repro.models import backbone as bb  # noqa: E402
from repro.models.cnn import extractor_apply  # noqa: E402
from repro.serve.engine import Request  # noqa: E402
from repro.serve.frontend import (  # noqa: E402
    FrontendConfig, Priority, StreamingFrontend)
from repro.serve.scheduler import SchedulerConfig  # noqa: E402
from repro.serve.telemetry import Telemetry  # noqa: E402

ARCH = "qwen2-0.5b"
LENGTHS = (64, 128, 512)     # pool KV width 1024 pages at 128 (Pallas
N_REQUESTS = 16              # decode kernel); 128 and 512 take chunked
NEW_TOKENS = 32              # prefill
N_REFERENCE = 4              # requests checked against a lone prefill

# First-token logits of the served path against a float32 prefill of the
# prompt alone at "highest" matmul precision, as a fraction of the
# reference logits' standard deviation.  The served path runs the TPU's
# default precision, which feeds float32 matmuls to the MXU as one bf16
# pass (8-bit mantissa, ~0.4% relative rounding per operand); through 24
# layers the error in a logit stays a few percent of the logits' spread,
# and the largest of 151936 logits sits several error-sigmas out.
LOGIT_TOL = 0.25

# AgileNN deployment forward, fused offload vs the two-pass jnp path:
# the same Remote NN runs on the same dequantized features (indices and
# values must match exactly), so logits agree to float32 rounding of
# differently fused elementwise work.
AGILE_RTOL = 1e-5
AGILE_ATOL = 1e-5


def log(*a) -> None:
    print(*a, flush=True)


class SmokeError(AssertionError):
    """A phase's output failed its check."""


def check(ok, msg: str) -> None:
    """Raise when a check fails (unlike `assert`, kept under -O)."""
    if not ok:
        raise SmokeError(msg)


class FirstLogits:
    """Wraps a scheduler's inject program to keep, per request id, the
    prefill logits its first token is sampled from.  Only references to
    the (never donated) logits are kept; they are read after the run."""

    def __init__(self, sched):
        self.sched = sched
        self.inner = sched._inject
        self.by_rid: dict = {}
        sched._inject = self

    def __call__(self, pool, slots, rows, logits0, *rest):
        owners = self.sched._slot_rid
        for i, s in enumerate(np.asarray(slots)):
            if s < len(owners):
                self.by_rid[owners[s]] = logits0[i]
        return self.inner(pool, slots, rows, logits0, *rest)

    def _cache_size(self) -> int:          # telemetry's compile counter
        return self.inner._cache_size()


def reference_logits(cfg, params, prompts):
    """Last-token logits of a lone float32 prefill per prompt."""
    out = []
    with jax.default_matmul_precision("highest"):
        pre = jax.jit(partial(bb.prefill, cfg), static_argnames=("max_len",))
        for t in prompts:
            lg, _, _ = pre(params, {"tokens": jnp.asarray(t[None], jnp.int32)},
                           max_len=len(t))
            out.append(jax.device_get(lg[0]))
    return out


def compare_logits(got, want, tag: str) -> dict:
    """Check one request's first-token logits within LOGIT_TOL; returns
    the numbers compared.  Where the reference's top-2 margin exceeds
    the tolerance, the greedy tokens must agree."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(np.all(np.isfinite(got)), f"{tag}: non-finite logits")
    tol = LOGIT_TOL * want.std()
    err = float(np.abs(got - want).max())
    top2 = np.sort(want)[-2:]
    margin = float(top2[1] - top2[0])
    same = int(np.argmax(got)) == int(np.argmax(want))
    rec = {"max_err": err, "tol": float(tol), "ref_std": float(want.std()),
           "margin": margin, "greedy_equal": same}
    log(f"  {tag}: max|dlogit| {err:.4g} (tol {tol:.4g}, ref std "
        f"{want.std():.4g}), top-2 margin {margin:.4g}, greedy equal {same}")
    check(err <= tol, f"{tag}: max|dlogit| {err} > tol {tol}")
    if margin > tol:
        check(same, f"{tag}: greedy token differs at margin {margin}")
    return rec


def check_tokens(toks, vocab: int, n: int, tag: str) -> None:
    toks = np.asarray(toks)
    check(len(toks) == n, f"{tag}: {len(toks)} tokens, expected {n}")
    check(toks.min() >= 0 and toks.max() < vocab,
          f"{tag}: token out of range")


def chunk_has_kernel(sched) -> bool:
    """Whether the scheduler's decode-chunk program holds a compiled
    Mosaic kernel (interpret mode lowers to plain HLO instead)."""
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding)
    S = len(sched._slot_rid)
    with (jax.set_mesh(sched.mesh) if sched.mesh is not None
          else nullcontext()):
        text = sched._chunk.lower(
            jax.tree.map(shape, sched.params), jax.tree.map(shape, sched._pool),
            jax.ShapeDtypeStruct((S,), bool), jax.random.PRNGKey(0),
            jnp.int32(1)).as_text()
    return "tpu_custom_call" in text


def serve_queue(cfg, params, tel, *, mesh=None):
    """Phase (b)'s queue: returns (engine, requests, completions,
    {request index: first-token logits})."""
    eng, reqs = queue_workload(cfg, params, n=N_REQUESTS, lengths=LENGTHS,
                               tokens=NEW_TOKENS, mesh=mesh, telemetry=tel)
    sched = eng.scheduler
    cap = FirstLogits(sched)
    base = sched._next_rid
    outs = eng.generate(reqs)
    sched.export_metrics()
    first = {i: cap.by_rid[base + i] for i in range(len(reqs))}
    return eng, reqs, outs, first


# ------------------------------------------------------------ phases --


def phase_queue(cfg, params, tel) -> None:
    """(b)+(c): the mixed-length queue, checked against lone prefills."""
    eng, reqs, outs, first = serve_queue(cfg, params, tel)
    lens = [len(r.tokens) for r in reqs]
    check(set(lens) == set(LENGTHS), f"queue missed a bucket: {lens}")
    for i, c in enumerate(outs):
        check_tokens(c.tokens, cfg.vocab, NEW_TOKENS, f"request {i}")
    log(f"  served {len(outs)} requests, prompt lengths {sorted(set(lens))}, "
        f"{sum(len(c.tokens) for c in outs)} tokens")
    kernel = chunk_has_kernel(eng.scheduler)
    log(f"  _chunk program holds tpu_custom_call: {kernel}")
    check(kernel, "decode chunk runs without the compiled Pallas kernel")

    # reference: the first request of each length, then the next ones
    picks = [lens.index(n) for n in LENGTHS]
    picks += [i for i in range(len(reqs)) if i not in picks]
    picks = picks[:N_REFERENCE]
    refs = reference_logits(cfg, params, [reqs[i].tokens for i in picks])
    recs = {}
    for i, want in zip(picks, refs):
        got = jax.device_get(first[i])
        recs[i] = compare_logits(got, want, f"request {i} (len {lens[i]})")
    for i, lg in first.items():
        lg = jax.device_get(lg)
        check(np.all(np.isfinite(lg)), f"request {i}: NaN")
        check(int(outs[i].tokens[0]) == int(np.argmax(lg)),
              f"request {i}: first token is not the argmax of its logits")

    # the equal-length fast path: one while_loop per batch
    same = [r for r in reqs if len(r.tokens) == LENGTHS[0]][:4]
    fast = eng.generate(same)
    for i, c in enumerate(fast):
        check_tokens(c.tokens, cfg.vocab, NEW_TOKENS, f"fast-path row {i}")
    j = picks[0]                  # first request of the shortest length
    if recs[j]["margin"] > recs[j]["tol"]:
        check(int(fast[0].tokens[0]) == int(np.argmax(refs[0])),
              "fast path's first token differs from the reference")
    log(f"  fast path: {len(fast)} equal-length rows x {NEW_TOKENS} tokens")


def phase_frontend(cfg, params, tel) -> None:
    """(b): streaming frontend with preemption and the prefix cache, so
    suspend, parking and page seeding run with donation active."""
    rng = np.random.RandomState(1)
    sc = SchedulerConfig(buckets=(64, 128, 256), preempt=True,
                         prefix_cache=True)
    header = rng.randint(0, cfg.vocab, sc.page_size)   # one shared page
    fe = StreamingFrontend(
        cfg, params, sched=sc, max_len=384, telemetry=tel,
        frontend=FrontendConfig(max_queue=16, feed_depth=1,
                                preempt_wait_ms=0.0))

    def req(n_new):
        tail = rng.randint(0, cfg.vocab, 24)
        return Request(tokens=np.concatenate([header, tail]),
                       max_new_tokens=n_new)

    hog_new, inter_new = 96, 8
    hogs = [fe.submit(req(hog_new), Priority.BEST_EFFORT)
            for _ in range(sc.max_slots)]
    while fe.sched._free_slots() and fe.has_work():
        fe.step()
    inter = fe.submit(req(inter_new), Priority.INTERACTIVE)
    results = fe.run()
    for rid in hogs + [inter]:
        status, toks = results[rid]
        check(status == "served", f"frontend request {rid}: {status}")
        check_tokens(toks, cfg.vocab,
                     inter_new if rid == inter else hog_new,
                     f"frontend request {rid}")
    m = tel.metrics
    preempted = sum(c.n for c in m.instruments()
                    if getattr(c, "name", "") == "frontend.preempted")
    resumed = m.counter("sched.resumed").n
    hit_rate = fe.sched.prefix.hit_rate
    log(f"  frontend: {len(results)} served, {preempted} preempted, "
        f"{resumed} resumed, prefix hit rate {hit_rate:.3f}")
    check(preempted >= 1 and resumed >= 1, "no preemption took place")
    check(hit_rate > 0, "no prompt was served from the prefix cache")


def phase_agile(seed: int) -> None:
    """(d): AgileNN's deployment forward at the paper's 96x96 size; the
    fused offload runs the compiled Pallas kernel on the chip."""
    cfg = AgileNNConfig(image_size=96)
    key, kimg = jax.random.split(jax.random.PRNGKey(seed))
    params = init_agile_params(cfg, key)
    C, k = cfg.extractor_channels, cfg.agile.k
    perm = tuple(int(p) for p in np.random.RandomState(seed).permutation(C))
    params["mapping"] = jnp.asarray(perm, jnp.int32)
    images = jax.random.uniform(kimg, (64, cfg.image_size, cfg.image_size, 3))

    logits, _ = agile_forward(cfg, params, images, train=False)
    logits = np.asarray(logits)
    want, _ = agile_forward(cfg, params, images, train=False, use_fused=False)
    want = np.asarray(want)
    check(logits.shape == (64, cfg.n_classes) and np.all(np.isfinite(logits)),
          "agile logits are malformed or not finite")
    np.testing.assert_allclose(logits, want, rtol=AGILE_RTOL, atol=AGILE_ATOL)

    raw = extractor_apply(params["extractor"], images)
    centers = params["quant"]["centers"]
    got = fused_offload(raw, centers, perm=perm, k=k)
    ref = fused_offload_jnp(raw, centers, perm=perm, k=k)
    names = ("local", "remote", "indices", "dequantized")
    for name, g, r in zip(names, got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=name)
    text = fused_offload_op.lower(raw, centers, perm=perm, k=k,
                                  interpret=False).as_text()
    kernel = "tpu_custom_call" in text
    rows = raw.size // C
    log(f"  agile: {raw.shape[0]} images {cfg.image_size}x{cfg.image_size}, "
        f"{rows} feature rows x C {C} (k {k}, {centers.shape[0]} centers); "
        f"fused == jnp on all four outputs; logits max|d| "
        f"{np.abs(logits - want).max():.3g}; tpu_custom_call {kernel}")
    check(kernel, "fused offload runs without the compiled Pallas kernel")


def phase_mesh(cfg, params, tel) -> None:
    """--chips 4: the queue on a (data 2, model 2) mesh against the same
    queue on one chip of this process."""
    n_dev = len(jax.devices())
    check(n_dev == 4, f"--chips 4 needs four devices, found {n_dev}")
    mesh = make_serving_mesh(data=2, model=2)
    eng_m, reqs, outs_m, first_m = serve_queue(cfg, params, tel, mesh=mesh)
    sched = eng_m.scheduler
    devs = set(jax.devices())
    for name, tree in (("params", sched.params), ("pool", sched._pool)):
        leaves = jax.tree.leaves(tree)
        check(all(set(x.sharding.device_set) == devs for x in leaves),
              f"{name}: a leaf is not placed on all four devices")
    split = [x for x in (sched.params["embed"]["table"],
                         sched._pool["cache"]["k"], sched._pool["cache"]["v"])
             if x.sharding.shard_shape(x.shape) != x.shape]
    check(len(split) == 3, "embedding or KV cache is not sharded")
    kernel = chunk_has_kernel(sched)
    log(f"  mesh {dict(mesh.shape)}: params and pool on {len(devs)} devices; "
        f"_chunk holds tpu_custom_call: {kernel}")
    check(kernel, "meshed decode chunk runs without the Pallas kernel")

    _, _, outs_1, first_1 = serve_queue(cfg, params, tel)
    first_equal = all_equal = 0
    errs = []
    for i in range(len(reqs)):
        rec = compare_logits(jax.device_get(first_m[i]),
                             jax.device_get(first_1[i]), f"request {i}")
        errs.append(rec["max_err"])
        check_tokens(outs_m[i].tokens, cfg.vocab, NEW_TOKENS, f"mesh {i}")
        first_equal += int(outs_m[i].tokens[0] == outs_1[i].tokens[0])
        all_equal += int(np.array_equal(outs_m[i].tokens, outs_1[i].tokens))
    log(f"  mesh vs one chip: first tokens equal {first_equal}/{len(reqs)}, "
        f"whole sequences equal {all_equal}/{len(reqs)}, worst max|dlogit| "
        f"{max(errs):.4g}")


# -------------------------------------------------------------- main --


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    cfg = get_config(ARCH)
    tel = Telemetry(enabled=True)
    wall: dict = {}
    failed = []
    t_all = time.perf_counter()

    def run(name, fn, *a):
        t0 = time.perf_counter()
        log(f"phase {name}")
        try:
            fn(*a)
            ok = True
        except Exception:
            traceback.print_exc()
            failed.append(name)
            ok = False
        dt = time.perf_counter() - t0
        log(f"phase {name}: {'ok' if ok else 'FAILED'} in {dt:.3f}s")
        wall[name] = dt

    params = {}

    def init():
        params["p"] = jax.block_until_ready(jax.jit(
            partial(bb.init_params, cfg))(jax.random.PRNGKey(args.seed)))
        n = sum(x.size for x in jax.tree.leaves(params["p"]))
        log(f"  {ARCH}: {n} params in {cfg.param_dtype}, d_model "
            f"{cfg.d_model}, {cfg.n_layers} layers, heads {cfg.n_heads}/"
            f"{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab}")

    run("init", init)
    if "init" not in failed:
        if args.chips == 4:
            run("mesh", phase_mesh, cfg, params["p"], tel)
        else:
            run("queue", phase_queue, cfg, params["p"], tel)
            run("frontend", phase_frontend, cfg, params["p"], tel)
    if args.chips == 1:
        run("agile", phase_agile, args.seed)

    compiles = {}
    for c in tel.metrics.instruments():
        if c.name.startswith("jit.") and c.name.endswith(".compiles"):
            lab = ",".join(f"{k}={v}" for k, v in c.labels)
            compiles[f"{c.name}{{{lab}}}"] = c.n
    for k in sorted(compiles):
        log(f"  {k} {compiles[k]}")
    log(f"jit compiles {sum(compiles.values())}; persistent cache hits "
        f"{cache['hits']}, misses {cache['misses']}")
    log(f"wall {time.perf_counter() - t_all:.3f}s: " + ", ".join(
        f"{k} {v:.3f}s" for k, v in wall.items()))
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
