"""Smoke run of the main path on one TPU: tune, kernels, model, serving.

    python chip_smoke.py [--seed N]

One process, no fallback.  The phases run in this order, and any failure
exits non-zero:

  device   the default device is a TPU whose ``device_kind`` has hardware
           parameters (``core.device_model.DEVICE_KINDS``);
  tune     drivers for the four tier-1 kernel specs and the two
           introspected kernels, built from source with the repository's
           oracle (``V5eSimulator`` over this chip's parameters) into
           ``<checkout>/.klaraptor_cache``, which is emptied first;
  kernels  every op of ``kernels/ops.py`` with ``use_pallas=True`` at real
           widths: a tuned config, compiled to a Mosaic kernel
           (``tpu_custom_call``), matching ``kernels/ref.py``;
  model    llama3.2-1b at full width with random weights from ``--seed``:
           the logits of forward + unembed at 16 positions with the Pallas
           kernels are as close to an f32 reference as the bf16 XLA path,
           for all 16 layers and for the first layer alone;
  serving  8 requests through ``launch.serve.build_engine``, then a prefill
           through the KV cache held to the same test.

Compile seconds, token rates and peak memory on the lines before the last
are informational.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
KLARAPTOR_CACHE = os.path.join(ROOT, ".klaraptor_cache")

# Model phase: one prefill-sized batch.  Serving phase: decode slots, cache
# length, and the request mix.
MODEL_BATCH, MODEL_SEQ = 2, 1024
LOGIT_POSITIONS = 16
SERVE_BATCH, SERVE_MAX_SEQ = 4, 512
SERVE_PROMPTS = tuple(range(8, 65, 8))          # 8 prompts, 8..64 tokens
SERVE_MAX_NEW = 16

# Kernel tolerances, as the largest |out - ref| over the largest |ref|.
#  * 1e-2 where both sides round one bf16 result from f32 accumulators that
#    differ only in summation order: one bf16 ulp is 2^-8 of the value.
#  * 2e-2 for attention and the SSD scan: the kernels round probabilities /
#    gates through bf16 or reassociate exp-weighted sums by chunk, a few
#    ulps more than a single rounding.  A wrong mask, tile offset or carried
#    state is off by order one.
#  * 1e-4 for column sums: f32 accumulation of bf16 values, exact inputs.
TOL_ROUNDED = 1e-2
TOL_ATTENTION = 2e-2
TOL_SUM = 1e-4
# Model logits are judged against an f32 reference (the same forward with
# f32 weights at full matmul precision), by relative RMS error over the
# vocabulary.  The bf16 XLA path sets the scale, and a path under test --
# the Pallas kernels, the KV cache -- may be at most LOGITS_FACTOR times as
# far.  Both compute in bf16 with f32 accumulation, so their distances are
# of one size.  With random weights the token embeddings (std 0.02) are a
# small part of a residual stream that every block adds an order-one term
# to, and from the second layer on bf16 rounding grows to about 0.3 at 16
# layers (TPU v5e) for either path; there a fault could hide.  So each check
# runs twice: on the whole model, and on its first layer, where both paths
# sit under 0.1 from the reference (0.074 on a v5e) and a kernel or cache
# fault, order one, cannot.
LOGITS_FACTOR = 2.0


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    """The default device, which must be a TPU; anything else exits."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX's default device is "
            f"{dev.platform!r} ({dev.device_kind}). There is no CPU path.")
    return dev


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if not (np.isfinite(out).all() and np.isfinite(ref).all()):
        return float("inf")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def rel_rms(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if not (np.isfinite(out).all() and np.isfinite(ref).all()):
        return float("inf")
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


def check(name: str, err: float, tol: float) -> None:
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3g} exceeds {tol:g}")


def check_logits(name: str, got, bf16, ref) -> str:
    """``got`` against the f32 reference, on the scale the bf16 XLA path
    sets; returns a report line."""
    scale = rel_rms(bf16, ref)
    err = rel_rms(got, ref)
    check(f"{name} vs f32 reference", err, LOGITS_FACTOR * scale)
    return (f"relative RMS error vs f32 reference {err:.4g} "
            f"(bf16 XLA path: {scale:.4g}, limit {LOGITS_FACTOR:g}x that)")


def require_mosaic(name: str, compiled) -> None:
    """Pallas ran as a Mosaic kernel, not the reference in its place."""
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in compiled HLO")


def compile_with_choices(fn, args):
    """AOT-compile ``jax.jit(fn)`` on ``args``; returns the executable, its
    compile seconds, and the launch decisions its trace made."""
    import jax

    from repro.core.driver import get_choice_listener, set_choice_listener

    events = []
    prev = get_choice_listener()
    set_choice_listener(events.append)
    try:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        seconds = time.perf_counter() - t0
    finally:
        set_choice_listener(prev)
    return compiled, seconds, events


def require_tuned(name: str, events) -> None:
    if not events:
        raise AssertionError(f"{name}: no launch decision was made")
    for ev in events:
        log(f"  {ev.kernel} at {dict(ev.D)}: config {dict(ev.config)} "
            f"(source: {ev.source})")
        if ev.source != "driver":
            raise AssertionError(
                f"{name}: {ev.kernel} launched with a {ev.source} config, "
                f"not a tuned one")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_tune(hw, llama, mamba) -> None:
    """Build the drivers from source against this chip's parameters."""
    from repro.core import (Klaraptor, V5eSimulator, flash_attention_spec,
                            matmul_spec, moe_gmm_spec, ssd_scan_spec)
    from repro.introspect import auto_register
    from repro.kernels.layernorm import layernorm_grid_spec, layernorm_pallas
    from repro.kernels.reduce import colsum_grid_spec, colsum_pallas

    shutil.rmtree(KLARAPTOR_CACHE, ignore_errors=True)
    tuner = Klaraptor(V5eSimulator(hw), hw=hw)
    specs = [
        matmul_spec(),
        flash_attention_spec(head_dim=llama.head_dim),
        moe_gmm_spec(),
        ssd_scan_spec(d_head=mamba.mamba_head_dim, d_state=mamba.ssm_state),
        auto_register(layernorm_pallas, layernorm_grid_spec(llama.d_model),
                      hw=hw).spec,
        auto_register(colsum_pallas, colsum_grid_spec(), hw=hw).spec,
    ]
    for spec in specs:
        build = tuner.build_driver(spec)
        if build.from_cache:
            raise AssertionError(f"{spec.name}: driver came from a cache")
        log(f"[tune] {spec.name}: built for {hw.name} in "
            f"{build.build_wall_seconds:.2f} s host time from "
            f"{build.collected.n_probe_executions} simulated probes")


def kernel_cases(llama, mamba, moe_cfg, key):
    """(name, widths' source, op, reference, args, tolerance) per op."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.models.layers import moe_capacity

    keys = iter(jax.random.split(key, 32))
    bf = jnp.bfloat16

    def normal(shape, scale=1.0, dtype=bf):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    tokens = MODEL_BATCH * MODEL_SEQ
    d, f = llama.d_model, llama.d_ff
    hq, hkv, dh = llama.n_heads, llama.n_kv_heads, llama.head_dim
    attn = dict(num_q_heads=hq, num_kv_heads=hkv, causal=True)
    yield ("matmul", f"{llama.name} MLP up-projection",
           functools.partial(ops.matmul, use_pallas=True), ref.matmul_ref,
           (normal((tokens, d)), normal((d, f), d ** -0.5)), TOL_ROUNDED)
    yield ("flash_attention", f"{llama.name} attention",
           functools.partial(ops.flash_attention, use_pallas=True, **attn),
           functools.partial(ref.flash_attention_ref, **attn),
           (normal((MODEL_BATCH * hq, MODEL_SEQ, dh)),
            normal((MODEL_BATCH * hkv, MODEL_SEQ, dh)),
            normal((MODEL_BATCH * hkv, MODEL_SEQ, dh))), TOL_ATTENTION)
    e, g = moe_cfg.n_experts, moe_capacity(moe_cfg, moe_cfg.moe_group)
    k, n = moe_cfg.d_model, moe_cfg.moe_d_ff
    yield ("moe_gmm", f"{moe_cfg.name} expert up-projection",
           functools.partial(ops.moe_gmm, use_pallas=True), ref.moe_gmm_ref,
           (normal((e, g, k)), normal((e, k, n), k ** -0.5)), TOL_ROUNDED)
    bh = MODEL_BATCH * mamba.mamba_heads
    yield ("ssd_scan", f"{mamba.name} SSD mixer",
           functools.partial(ops.ssd_scan, use_pallas=True), ref.ssd_scan_ref,
           (normal((bh, MODEL_SEQ, mamba.mamba_head_dim)),
            jax.nn.softplus(normal((bh, MODEL_SEQ), dtype=jnp.float32)),
            normal((bh, MODEL_SEQ, mamba.ssm_state)),
            normal((bh, MODEL_SEQ, mamba.ssm_state)),
            -jnp.exp(normal((bh,), 0.5, jnp.float32))), TOL_ATTENTION)
    yield ("layernorm", f"{llama.name} residual stream",
           functools.partial(ops.layernorm, use_pallas=True),
           ref.layernorm_ref,
           (normal((tokens, d)), normal((tokens, d)),
            normal((d,), dtype=jnp.float32), normal((d,), dtype=jnp.float32)),
           TOL_ROUNDED)
    yield ("blocked_colsum", f"{llama.name} residual stream",
           functools.partial(ops.blocked_colsum, use_pallas=True),
           ref.colsum_ref, (normal((tokens, d)),), TOL_SUM)


def phase_kernels(llama, mamba, moe_cfg, key) -> None:
    import jax

    for name, source, op, ref_fn, args, tol in kernel_cases(
            llama, mamba, moe_cfg, key):
        shapes = " ".join(str(tuple(a.shape)) for a in args)
        log(f"[kernels] {name} at {source} widths {shapes}")
        compiled, seconds, events = compile_with_choices(op, args)
        require_tuned(name, events)
        require_mosaic(name, compiled)
        out = compiled(*args)
        # The reference at full f32 matmul precision: the default would
        # round f32 operands through bf16 on the MXU.
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        err = rel_err(out, want)
        check(name, err, tol)
        log(f"  tpu_custom_call compiled in {seconds:.2f} s; "
            f"max error {err:.3g} of max |ref| (tolerance {tol:g})")


def logits_fn(cfg, sharder, positions):
    """jit(params, tokens) -> (B, len(positions), V) logits at ``positions``,
    through the cache-free forward + unembed."""
    import jax.numpy as jnp

    from repro.models import transformer as T

    def fn(params, tokens):
        x = T.embed_tokens(cfg, params, tokens)
        hidden, _ = T.forward(cfg, params, x, sharder)
        return T.unembed(cfg, params, hidden[:, jnp.asarray(positions)])
    return fn


def reference_logits(cfg, params, sharder, tokens, positions):
    """Logits at ``positions`` of the XLA forward with f32 weights at full
    matmul precision (a TPU rounds f32 matmuls through bf16 otherwise)."""
    import jax
    import jax.numpy as jnp

    c = cfg.replace(dtype=jnp.float32, use_pallas=False)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(logits_fn(c, sharder, positions))(
            p32, tokens))


def first_layers(cfg, params, n_layers: int):
    """The model cut to its first ``n_layers`` layers, at full width (one
    block per layer group, as in llama)."""
    import jax

    if n_layers == cfg.n_layers:
        return cfg, params
    blocks = jax.tree.map(lambda a: a[:n_layers], params["blocks"])
    return cfg.replace(n_layers=n_layers), {**params, "blocks": blocks}


def phase_model(cfg, params, sharder, key, device_kind: str) -> None:
    """Forward + unembed with and without the Pallas kernels, against the
    f32 reference, at full depth and at the first layer."""
    import jax

    tokens = jax.random.randint(key, (MODEL_BATCH, MODEL_SEQ), 2,
                                cfg.vocab_size, dtype=jax.numpy.int32)
    # Evenly spaced positions, the last included: the last query alone sees
    # no masked keys, so a fault in the causal mask would not reach it.
    stride = MODEL_SEQ // LOGIT_POSITIONS
    positions = tuple(range(stride - 1, MODEL_SEQ, stride))
    for depth in (cfg.n_layers, 1):
        c_depth, p_depth = first_layers(cfg, params, depth)
        logits = {}
        for use_pallas in (True, False):
            c = c_depth.replace(use_pallas=use_pallas)
            compiled, seconds, events = compile_with_choices(
                logits_fn(c, sharder, positions), (p_depth, tokens))
            if use_pallas:
                require_tuned("model forward", events)
                require_mosaic("model forward", compiled)
            t0 = time.perf_counter()
            logits[use_pallas] = np.asarray(compiled(p_depth, tokens))
            run_s = time.perf_counter() - t0
            log(f"[model] {cfg.name} {depth} layers, use_pallas="
                f"{use_pallas}: compiled in {seconds:.1f} s, ran in "
                f"{run_s:.3f} s on {device_kind} (first call)")
        ref = reference_logits(c_depth, p_depth, sharder, tokens,
                               positions)
        report = check_logits(f"model logits, {depth} layers", logits[True],
                              logits[False], ref)
        same = float(np.mean(logits[True].argmax(-1) == ref.argmax(-1)))
        log(f"[model] {depth} layers, logits {logits[True].shape} at "
            f"{len(positions)} positions, Pallas path: {report}; argmax agrees "
            f"with the reference at {same:.0%} of them; Pallas vs bf16 XLA "
            f"path {rel_rms(logits[True], logits[False]):.4g}")


def phase_serving(cfg, params, sharder, seed: int, device_kind: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import build_engine
    from repro.models import Model
    from repro.serving import Request

    engine = build_engine(cfg, SERVE_BATCH, SERVE_MAX_SEQ, params=params,
                          seed=seed)
    rng = np.random.default_rng(seed)
    for rid, n in enumerate(SERVE_PROMPTS):
        prompt = rng.integers(2, cfg.vocab_size, n).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=SERVE_MAX_NEW))
    t0 = time.perf_counter()
    finished = sorted(engine.run(), key=lambda r: r.rid)
    wall = time.perf_counter() - t0
    if len(finished) != len(SERVE_PROMPTS):
        raise AssertionError(
            f"serving: {len(finished)} of {len(SERVE_PROMPTS)} finished")
    for r in finished:
        out = r.output
        ended = len(out) == SERVE_MAX_NEW or (out and out[-1] ==
                                               engine.eos_id)
        if not (r.done and ended and all(0 <= t < cfg.vocab_size
                                         for t in out)):
            raise AssertionError(f"serving: request {r.rid} -> {out}")
        log(f"[serving] request {r.rid}: {len(r.prompt)} prompt tokens -> "
            f"{len(out)} generated")
    generated = sum(len(r.output) for r in finished)
    log(f"[serving] {generated} tokens for {len(finished)} requests in "
        f"{wall:.2f} s on {device_kind}, prefill token by token and "
        f"decode-step compilation included ({generated / wall:.1f} tok/s)")

    # The cache path: prefill through decode steps against the cache-free
    # forward, on the longest prompt, at full depth and at the first layer.
    tokens = jnp.asarray([finished[-1].prompt], jnp.int32)
    for depth in (cfg.n_layers, 1):
        c, p = first_layers(cfg, params, depth)
        model = Model(c)
        cache = model.init_cache(1, SERVE_MAX_SEQ)
        prefill = jax.jit(
            lambda p, t, cache: model.prefill(p, t, cache, sharder)[0])
        got = np.asarray(prefill(p, tokens, cache))
        last = (tokens.shape[1] - 1,)
        bf16 = np.asarray(jax.jit(logits_fn(
            c.replace(use_pallas=False), sharder, last))(p, tokens))[:, 0]
        ref = reference_logits(c, p, sharder, tokens, last)[:, 0]
        report = check_logits(f"prefill cache logits, {depth} layers", got,
                              bf16, ref)
        log(f"[serving] prefill through the KV cache ({tokens.shape[1]} "
            f"tokens, {depth} layers): {report}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["KLARAPTOR_CACHE_DIR"] = KLARAPTOR_CACHE
    import jax

    from repro.configs import get_config
    from repro.core.device_model import hardware_for
    from repro.distributed.sharding import Sharder
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import Model, init_params

    cache_dir = use_compile_cache()
    hw = hardware_for(dev.device_kind)
    kind = dev.device_kind
    log(f"[device] {dev.platform} {kind} x{len(jax.devices())}: "
        f"hardware parameters {hw.name}, scoped VMEM budget "
        f"{hw.vmem_limit_bytes >> 20} MiB; compile cache {cache_dir}")

    llama = get_config("llama3.2-1b")
    mamba = get_config("mamba2-130m")
    moe_cfg = get_config("qwen3-moe-235b-a22b")
    key = jax.random.PRNGKey(args.seed)
    k_kernels, k_params, k_tokens = jax.random.split(key, 3)

    phase_tune(hw, llama, mamba)
    phase_kernels(llama, mamba, moe_cfg, k_kernels)

    cfg = llama.replace(use_pallas=True)
    t0 = time.perf_counter()
    specs = Model(cfg).specs()
    params = jax.block_until_ready(
        jax.jit(lambda k: init_params(specs, k))(k_params))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    log(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {n_params / 1e9:.2f} B random parameters "
        f"from seed {args.seed} in {time.perf_counter() - t0:.1f} s")
    sharder = Sharder(mesh=None)
    phase_model(cfg, params, sharder, k_tokens, kind)
    phase_serving(cfg, params, sharder, args.seed, kind)

    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[device] peak_bytes_in_use {stats['peak_bytes_in_use']} on "
            f"{kind}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
